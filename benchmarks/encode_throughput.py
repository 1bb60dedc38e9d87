"""Write-path throughput: host encode vs the device encode pipeline.

The read path got the paper's optimizations; this table asks whether the
write path keeps up.  For each dataset/size cell it times end-to-end
``Codec.compress`` under ``encode_backend="ref"`` (the host path: float64
prequantization, numpy histogram) and ``encode_backend="jnp"`` (the
device pipeline the Pallas kernels implement: in-graph f32 quantize ->
outlier gather -> device histogram -> jit bit-pack, with only the
2*radius-entry histogram crossing to host for codebook construction).
Before timing, each cell decode-verifies the device-encoded payload
against the input within ``eb_effective`` -- the speedup is never bought
with a wrong stream.  (Byte-identity is asserted by the encode parity
matrix in tests/ on lattice-aligned inputs; on arbitrary data the f32
in-graph quantizer may tie-round a handful of codes differently from the
f64 host prequantizer, both within bound.)

GB/s is raw input bytes over wall time (the write-path twin of the
decoder tables' quant-code GB/s).  A ``compress_tree`` row times the
multi-tensor entry point the checkpoint/KV consumers actually call.  As
everywhere in this harness, timings are host wall-clock of the jit'd
reference pipelines on JAX's default backend; the Pallas bit-pack kernel
runs the same phases and is held bit-exact to them by tests/.
"""

from __future__ import annotations

import jax
import numpy as np

from benchmarks import common as Cm
from benchmarks import datasets as DS

from repro.core import Codec, CodecConfig
from repro.core.huffman import pipeline as hp

#: Input sizes in float32 elements (1 MiB and 4 MiB).
SIZES = (1 << 18, 1 << 20)


def _block_compress(codec, x):
    c = codec.compress(x)
    # Compressed is a host container, not a pytree: block on the arrays the
    # encode actually produced.
    jax.block_until_ready((c.stream.units, c.outlier_pos))
    return c


def run(n: int = DS.DEFAULT_N, quick: bool = False):
    del n  # sized by SIZES: the write path is the variable here
    rows = []
    names = list(DS.PAPER_RATIOS)[:1] if quick else list(DS.PAPER_RATIOS)[:3]
    sizes = SIZES[:1] if quick else SIZES
    for name in names:
        for sz in sizes:
            x, _ = DS.make_dataset(name, sz)
            raw = x.size * 4
            cells = {}
            for backend in ("ref", "jnp"):
                codec = Codec(CodecConfig(encode_backend=backend))
                cells[backend] = (codec, _block_compress(codec, x))
            c_dev = cells["jnp"][1]
            err = float(np.max(np.abs(
                np.asarray(cells["jnp"][0].decompress(c_dev)).reshape(-1)
                - x.reshape(-1))))
            assert err <= c_dev.eb_effective, (name, sz, err)

            mib = raw // (1 << 20)
            times = {}
            for backend, (codec, c) in cells.items():
                t = Cm.timeit(lambda codec=codec: _block_compress(codec, x))
                times[backend] = t
                derived = (f"CR={c.ratio:.2f};GBps={Cm.gbps(raw, t):.3f}")
                if backend == "jnp":
                    derived += f";host_vs_device={times['ref'] / t:.2f}"
                rows.append((f"encode/{name}/{mib}MiB/{backend}",
                             t * 1e6, derived))

    # Multi-tensor write path (what checkpoint shards / KV eviction call).
    x0, _ = DS.make_dataset(names[0], sizes[0])
    tree = {"a": x0, "b": x0[: x0.size // 2] * 0.5}
    for backend in ("ref", "jnp"):
        codec = Codec(CodecConfig(encode_backend=backend))

        def run_tree(codec=codec):
            ct = codec.compress_tree(tree)
            jax.block_until_ready((ct["a"].stream.units,
                                   ct["b"].stream.units))
            return ct

        t = Cm.timeit(run_tree)
        be = hp.get_encode_backend(backend)
        be.reset_stats()
        run_tree()   # counters for exactly one tree walk
        rows.append((f"encode/compress_tree/{backend}", t * 1e6,
                     f"leaves=2;encode_dispatches="
                     f"{be.stats['encode_dispatches']};encode_fallbacks="
                     f"{be.stats['encode_fallbacks']}"))
    return rows
