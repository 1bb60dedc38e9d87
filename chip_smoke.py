#!/usr/bin/env python3
"""Smoke test of the codec's main path on a TPU, with compiled kernels.

Every phase runs through the entry points a user calls (``Codec`` ->
``repro.store`` -> a consumer) under one policy: Pallas decode and encode
backends, gap-array sync, fixed-tile decode-write, fused
decode->dequantize->reconstruct.

* field: a seeded 3-D float32 surrogate at Nyx's shape (512^3, 512 MiB) is
  compressed at rel eb 1e-3 into a ``.szt`` archive with ``ArchiveWriter``,
  reopened with ``Archive`` and decoded with ``read_all``.  Checks: error
  within ``eb_effective``; output bytes equal to the ``ref`` backend's
  decode of the same archive; no fused fallback; the Pallas encode equal
  to the jnp encode (same ``compressed_digest``).
* checkpoint: the parameters of qwen3-0.6b at its published widths (depth
  cut to ``LAYERS`` of its 28 layers; the embedding and every per-layer
  leaf keep their shapes), random from ``--seed`` and built on the
  device, go through
  ``CheckpointManager.save`` and ``restore``.  Checks: every compressed
  leaf within its bound, raw leaves bit-exact, output bytes equal to a
  ``ref``-backend restore, no fused fallback.
* ``--chips 4`` runs only the sharded phase: the same parameters saved in
  the mesh-sharded layout from a (4, 1) mesh, restored onto a (2, 2) mesh
  (another layout) and compared with the single-device restore of the same
  checkpoint; every leaf must land in its ``NamedSharding`` with shards on
  4 distinct devices.

Without a TPU the script fails.  ``--tiny`` is the CPU rehearsal: it sets
``JAX_PLATFORMS=cpu`` (Pallas kernels run in the interpreter) and shrinks
every shape.

    python chip_smoke.py [--seed N] [--chips 4] [--tiny]

Prints one JSON line per phase (its ``seconds`` are wall-clock smoke
timings with compilation included, not metrics), then, last,
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
Any failed check or exception exits non-zero before that line.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.abspath(__file__))

FIELD_SHAPE = (512, 512, 512)          # Nyx (benchmarks/datasets.py)
TINY_FIELD_SHAPE = (24, 40, 56)
ARCH = "qwen3-0.6b"
# Depth kept of the model's 28 layers.  The ``ref`` decode the checkpoint
# is compared with runs near 1 us per value on a v5e; at full depth (6e8
# values) it alone would take most of the run's 1200 s.
LAYERS = 4
EB = 1e-3


class SmokeFailure(RuntimeError):
    """A check of the smoke test failed."""


def _parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded-restore phase on 4 chips")
    ap.add_argument("--tiny", action="store_true",
                    help="CPU rehearsal: JAX_PLATFORMS=cpu, small shapes")
    return ap.parse_args(argv)


def _log(msg: str):
    print(f"[chip_smoke] {msg}", file=sys.stderr, flush=True)


class _Phase:
    """Collects a phase's record and failed checks; ``finish`` prints the
    record as one JSON line, then raises if any check failed."""

    def __init__(self, name: str):
        self.name = name
        self.record: dict = {"phase": name}
        self.seconds: dict = {}
        self.failures: list = []
        self._t = time.perf_counter()

    def lap(self, label: str):
        now = time.perf_counter()
        self.seconds[label] = now - self._t
        self._t = now
        _log(f"{self.name}: {label} {self.seconds[label]:.1f}s")

    def check(self, ok: bool, what: str):
        if not ok:
            self.failures.append(what)

    def finish(self):
        self.record["seconds"] = self.seconds
        self.record["seconds_note"] = ("wall-clock smoke timings, "
                                       "compilation included; not metrics")
        self.record["failed_checks"] = self.failures
        print(json.dumps(self.record), flush=True)
        if self.failures:
            raise SmokeFailure(f"{self.name}: " + "; ".join(self.failures))


def _same_bytes(a, b) -> bool:
    import numpy as np

    a, b = np.asarray(a), np.asarray(b)
    return (a.dtype == b.dtype and a.shape == b.shape
            and np.array_equal(a.view(np.uint8), b.view(np.uint8)))


def _decode_stats(codec) -> dict:
    keys = ("decode_write_dispatches", "fused_dispatches", "fused_fallbacks",
            "plan_builds", "decode_guard_trips", "encode_dispatches",
            "encode_fallbacks")
    s = codec.stats
    return {k: int(s[k]) for k in keys}


def _flat(tree, prefix="") -> dict:
    if isinstance(tree, dict):
        out = {}
        for k, v in tree.items():
            out.update(_flat(v, f"{prefix}.{k}" if prefix else str(k)))
        return out
    return {prefix: tree}


def field_phase(args, codec, ref_codec, workdir):
    import jax.numpy as jnp
    import numpy as np

    from repro.core import Codec
    from repro.core.cache import compressed_digest
    from repro.data.pipeline import smooth_field
    from repro.store import Archive, ArchiveWriter

    ph = _Phase("field")
    shape = TINY_FIELD_SHAPE if args.tiny else FIELD_SHAPE
    x = jnp.asarray(smooth_field(shape, seed=args.seed))
    x.block_until_ready()
    ph.lap("make_input")

    codec.reset_stats()
    c = codec.compress(x)
    c.stream.units.block_until_ready()
    ph.lap("compress")
    path = os.path.join(workdir, "field.szt")
    with ArchiveWriter(path, codec=codec) as w:
        w.add("field", c)
    ph.lap("write_archive")
    with Archive(path, codec=codec) as ar:
        xhat = ar.read_all()["field"]
        xhat.block_until_ready()
    ph.lap("read_all_pallas")
    stats = _decode_stats(codec)

    with Archive(path, codec=ref_codec) as ar:
        xref = ar.read_all()["field"]
        xref.block_until_ready()
    ph.lap("read_all_ref")
    c_jnp = Codec(codec.config.replace(encode_backend="jnp")).compress(x)
    c_jnp.stream.units.block_until_ready()
    ph.lap("compress_jnp")

    err = float(jnp.max(jnp.abs(xhat.astype(jnp.float32) - x)))
    bound = float(c.eb_effective)
    same_ref = _same_bytes(xhat, xref)
    same_enc = compressed_digest(c) == compressed_digest(c_jnp)
    ph.record.update({
        "shape": list(shape), "dtype": str(x.dtype),
        "ratio": float(c.ratio), "archive_bytes": os.path.getsize(path),
        "max_err": err, "eb_effective": bound,
        "equal_to_ref_decode": same_ref,
        "pallas_encode_equal_to_jnp": same_enc,
        "outliers": int((np.asarray(c.outlier_pos) >= 0).sum()),
        "stats": stats})
    ph.check(tuple(xhat.shape) == shape and xhat.dtype == x.dtype,
             f"decoded {xhat.dtype}{tuple(xhat.shape)}")
    ph.check(bool(np.isfinite(err)) and err <= bound,
             f"max error {err!r} > eb_effective {bound!r}")
    ph.check(same_ref, "compiled decode differs from the ref decode")
    ph.check(same_enc, "pallas encode differs from the jnp encode")
    ph.check(stats["fused_fallbacks"] == 0, "fused fallback counted")
    ph.check(stats["fused_dispatches"] >= 1, "no fused dispatch")
    ph.check(stats["encode_fallbacks"] == 0, "encode fallback counted")
    ph.check(stats["encode_dispatches"] >= 1, "no encode dispatch")
    ph.finish()


def _model_params(args):
    import jax

    from repro import configs
    from repro.models import transformer as T

    cfg = configs.get_config(ARCH)
    cfg = cfg.reduced() if args.tiny else dataclasses.replace(
        cfg, n_layers=LAYERS)
    params = jax.jit(T.init_model, static_argnums=1)(
        jax.random.PRNGKey(args.seed), cfg)
    jax.block_until_ready(params)
    return cfg, params


def _min_size(args) -> int:
    return 4096 if args.tiny else 65536


def checkpoint_phase(args, codec, ref_codec, workdir):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.checkpoint.manager import ARCHIVE_NAME, CheckpointManager
    from repro.store import Archive

    ph = _Phase("checkpoint")
    cfg, params = _model_params(args)
    ph.lap("init_params")
    ckdir = os.path.join(workdir, "ckpt")
    codec.reset_stats()
    CheckpointManager(ckdir, codec=codec,
                      compress_min_size=_min_size(args)).save(0, params)
    ph.lap("save")
    restored = CheckpointManager(ckdir, codec=codec).restore()
    jax.block_until_ready(restored["params"])
    ph.lap("restore_pallas")
    stats = _decode_stats(codec)
    ref = CheckpointManager(ckdir, codec=ref_codec).restore()
    jax.block_until_ready(ref["params"])
    ph.lap("restore_ref")

    step_dir = os.path.join(ckdir, "step_00000000")
    with open(os.path.join(step_dir, "manifest.json")) as f:
        entries = json.load(f)["entries"]
    orig = _flat(params)
    got = _flat(restored["params"])
    got_ref = _flat(ref["params"])
    leaves, worst, raw_bytes, sz_bytes = [], 0.0, 0, 0
    with Archive(os.path.join(step_dir, ARCHIVE_NAME)) as ar:
        for key, x in orig.items():
            kind = entries[f"params.{key}"]["kind"]
            y = got[key]
            ph.check(_same_bytes(y, got_ref[key]),
                     f"{key}: compiled restore differs from ref restore")
            if kind == "raw":
                ph.check(_same_bytes(y, x), f"{key}: raw leaf not bit-exact")
                continue
            bound = float(ar.read_chunk(f"params.{key}").eb_effective)
            err = float(jnp.max(jnp.abs(jnp.asarray(y) - x)))
            worst = max(worst, err / bound)
            raw_bytes += x.nbytes
            ph.check(err <= bound, f"{key}: max error {err!r} > {bound!r}")
            leaves.append({"name": key, "shape": list(x.shape),
                           "max_err": err, "eb_effective": bound})
    sz_bytes = os.path.getsize(os.path.join(step_dir, ARCHIVE_NAME))
    ph.record.update({
        "arch": cfg.name, "n_layers": cfg.n_layers, "d_model": cfg.d_model,
        "d_ff": cfg.d_ff, "vocab": cfg.vocab,
        "param_bytes": int(sum(np.asarray(x).nbytes for x in orig.values())),
        "compressed_leaves": len(leaves),
        "raw_leaves": len(orig) - len(leaves),
        "ratio": raw_bytes / max(sz_bytes, 1),
        "worst_err_over_bound": worst, "leaves": leaves, "stats": stats})
    ph.check(len(leaves) > 0, "no leaf was compressed")
    ph.check(stats["fused_fallbacks"] == 0, "fused fallback counted")
    ph.check(stats["fused_dispatches"] >= len(leaves), "missing fused dispatch")
    ph.check(stats["encode_fallbacks"] == 0, "encode fallback counted")
    ph.finish()


def sharded_phase(args, codec, devices, workdir):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding

    from repro.checkpoint.manager import CheckpointManager
    from repro.launch.mesh import make_host_mesh

    ph = _Phase("sharded")
    devs = devices[:4]
    save_mesh = make_host_mesh(4, 1, devices=devs)
    restore_mesh = make_host_mesh(2, 2, devices=devs)
    cfg, params = _model_params(args)
    ph.lap("init_params")
    ckdir = os.path.join(workdir, "sharded")
    mgr = CheckpointManager(ckdir, codec=codec,
                            compress_min_size=_min_size(args))
    codec.reset_stats()
    mgr.save(0, params, mesh=save_mesh, shard_count=4)
    ph.lap("save_sharded")
    sharded = mgr.restore(mesh=restore_mesh)
    jax.block_until_ready(sharded["params"])
    ph.lap("restore_on_mesh")
    stats = _decode_stats(codec)
    single = mgr.restore()
    ph.lap("restore_single_device")

    with open(os.path.join(ckdir, "step_00000000", "manifest.json")) as f:
        entries = json.load(f)["entries"]
    orig = _flat(params)
    got = _flat(sharded["params"])
    ref = _flat(single["params"])
    n_split = n_sharded_kind = 0
    for key, x in orig.items():
        y = got[key]
        kind = entries[f"params.{key}"]["kind"]
        n_sharded_kind += kind == "sz-sharded"
        ph.check(isinstance(y.sharding, NamedSharding)
                 and y.sharding.mesh.shape == restore_mesh.shape,
                 f"{key}: not on the restore mesh ({y.sharding})")
        on = {s.device for s in y.addressable_shards}
        ph.check(len(on) == 4, f"{key}: shards on {len(on)} devices")
        n_split += any(s.data.shape != y.shape for s in y.addressable_shards)
        ph.check(_same_bytes(y, ref[key]),
                 f"{key}: mesh restore differs from single-device restore")
        if kind == "raw":
            ph.check(_same_bytes(y, x), f"{key}: raw leaf not bit-exact")
        else:
            # Each tile is compressed under its own value range, never wider
            # than the leaf's.
            xn = np.asarray(x)
            bound = EB * float(xn.max() - xn.min()) + float(
                np.spacing(np.float32(np.abs(xn).max() + 1.0)))
            err = float(jnp.max(jnp.abs(jnp.asarray(ref[key]) - x)))
            ph.check(err <= bound, f"{key}: max error {err!r} > {bound!r}")
    ph.record.update({
        "arch": cfg.name, "n_layers": cfg.n_layers,
        "save_mesh": dict(save_mesh.shape),
        "restore_mesh": dict(restore_mesh.shape),
        "leaves": len(orig), "sharded_layout_leaves": n_sharded_kind,
        "leaves_split_across_devices": n_split,
        "devices": sorted(str(d) for d in devs), "stats": stats})
    ph.check(n_sharded_kind > 0, "no leaf used the sharded layout")
    ph.check(n_split > 0, "no leaf was split across devices")
    ph.check(stats["fused_fallbacks"] == 0, "fused fallback counted")
    ph.finish()


def main(argv=None) -> int:
    args = _parse(argv)
    if args.tiny:
        os.environ["JAX_PLATFORMS"] = "cpu"
        if args.chips == 4:
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "")
                + " --xla_force_host_platform_device_count=4").strip()
    sys.path.insert(0, os.path.join(ROOT, "src"))

    import jax

    from repro.core import Codec, CodecConfig
    from repro.runtime.compile_cache import enable_compile_cache

    devices = jax.devices()
    dev = devices[0]
    _log(f"devices: {len(devices)} x {dev.platform} {dev.device_kind}")
    if dev.platform != "tpu" and not args.tiny:
        _log("no TPU found (use --tiny for the CPU rehearsal)")
        return 2
    if len(devices) < args.chips:
        _log(f"--chips {args.chips} needs {args.chips} devices, "
             f"found {len(devices)}")
        return 2
    _log(f"compile cache: {enable_compile_cache()}")

    codec = Codec(CodecConfig(eb=EB, mode="rel", backend="pallas",
                              encode_backend="pallas", method="gap",
                              strategy="tile", fused=True))
    ref_codec = Codec(codec.config.replace(backend="ref"))
    with tempfile.TemporaryDirectory(dir=ROOT, prefix=".chip_smoke_") as wd:
        if args.chips == 4:
            sharded_phase(args, codec, devices, wd)
        else:
            field_phase(args, codec, ref_codec, wd)
            checkpoint_phase(args, codec, ref_codec, wd)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
