"""Quickstart: compress a scientific field, decompress it three ways.

    PYTHONPATH=src python examples/quickstart.py

All policy lives in one ``CodecConfig``: the error bound on the encode
side, the sync method / decode strategy / backend on the decode side.  A
``Codec`` is the configured session -- it also caches phase 1-3 decoder
plans by content digest, so decoding the same tensor twice only pays the
decode-write phase the second time.
"""

import numpy as np

from repro.core.api import Codec, CodecConfig
from repro.data.pipeline import smooth_field


def main():
    # A Lorenzo-predictable "simulation snapshot" (see benchmarks/datasets.py
    # for surrogates calibrated to the paper's eight datasets).
    x = smooth_field((512, 512), seed=0)
    print(f"input: {x.shape} float32, {x.nbytes / 2**20:.1f} MiB")

    codec = Codec()   # defaults: eb 1e-3 relative, gap-array, ref backend
    c = codec.compress(x)
    print(f"compressed: {c.compressed_bytes / 2**20:.2f} MiB "
          f"(ratio {c.ratio:.2f}x, eb {c.eb:.3e})")

    for method in ("gap", "selfsync", "naive_ref"):
        xh = np.asarray(Codec(CodecConfig(method=method)).decompress(c))
        err = np.abs(xh - x).max()
        print(f"decompress[{method:10s}]: max err {err:.3e} "
              f"(bound {c.eb_effective:.3e}) "
              f"{'OK' if err <= c.eb_effective else 'VIOLATION'}")

    # kernel path (Pallas: compiled on a TPU, interpreted on the CPU) with
    # the online tuner's per-CR-class tiles: one config, no flag soup.
    tuned = Codec(CodecConfig(backend="pallas", strategy="tuned"))
    xh = np.asarray(tuned.decompress(c))
    print(f"decompress[pallas-tuned]: max err {np.abs(xh - x).max():.3e}")

    # batched multi-tensor decode: one decode-write dispatch per CR class
    # across all tensors (how checkpoint shards / KV blocks restore).
    shards = [codec.compress(smooth_field((128, 512), seed=s))
              for s in range(4)]
    codec.reset_stats()
    outs = codec.decompress_batch(shards)
    print(f"decompress_batch[4 shards]: "
          f"{codec.stats['decode_write_dispatches']} class dispatches, "
          f"max err {max(float(np.abs(np.asarray(o) - smooth_field((128, 512), seed=s)).max()) for s, o in enumerate(outs)):.3e}")

    # pytree round trip: Compressed leaves in, decoded arrays out.
    tree = {"layer0": {"w": smooth_field((256, 64), seed=7)},
            "step": np.int32(3)}
    back = codec.decompress_tree(codec.compress_tree(tree))
    err = np.abs(np.asarray(back["layer0"]["w"]) - tree["layer0"]["w"]).max()
    print(f"compress_tree/decompress_tree: max err {err:.3e}, "
          f"step passthrough {int(back['step'])}")


if __name__ == "__main__":
    main()
