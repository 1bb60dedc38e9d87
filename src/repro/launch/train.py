"""Training driver: reduced configs run end-to-end on local hardware; the
full configs use the same code path under the production mesh.

Features exercised here (and by tests/test_train_e2e.py):
  * deterministic sharded data (skip-ahead resume)
  * checkpoint/restart (atomic, optionally SZ-compressed shards)
  * simulated preemption (--preempt-at N exits mid-run; rerunning resumes)
  * gradient compression (--grad-compress, explicit-DP path for small models)

Usage:
  PYTHONPATH=src python -m repro.launch.train --arch qwen3-0.6b --reduced \
      --steps 50 --batch 8 --seq 128 --ckpt-dir /tmp/ckpt
"""

from __future__ import annotations

import argparse
import sys
import time

import jax
import jax.numpy as jnp

from repro import configs
from repro.checkpoint.manager import CheckpointManager
from repro.data.pipeline import DataConfig, SyntheticLM
from repro.models import steps as S
from repro.models import transformer as T
from repro.optim import adamw
from repro.runtime.compile_cache import enable_compile_cache


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--n-micro", type=int, default=1)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--ckpt-compress-eb", type=float, default=None)
    ap.add_argument("--ckpt-sharded", action="store_true",
                    help="write checkpoints in the mesh-sharded layout "
                         "(docs/distributed.md) and restore directly into "
                         "the host mesh's shardings")
    ap.add_argument("--ckpt-shards", type=int, default=None,
                    help="shard archives per sharded checkpoint "
                         "(default: one per process)")
    ap.add_argument("--preempt-at", type=int, default=None,
                    help="simulate preemption: exit(17) after this step")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--opt-int8", action="store_true")
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = configs.get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    ocfg = adamw.AdamWConfig(
        lr=args.lr, state_dtype="int8" if args.opt_int8 else "float32")

    data = SyntheticLM(DataConfig(
        vocab=cfg.vocab, seq_len=args.seq, global_batch=args.batch,
        seed=args.seed))

    ckpt_codec = None
    if args.ckpt_compress_eb is not None or args.ckpt_sharded:
        from repro.core import Codec, CodecConfig
        # Sharded layout compresses per tile; default eb if none was given.
        ckpt_codec = Codec(CodecConfig(eb=args.ckpt_compress_eb or 1e-4))
    mgr = (CheckpointManager(args.ckpt_dir, codec=ckpt_codec)
           if args.ckpt_dir else None)
    ckpt_mesh = None
    if args.ckpt_sharded:
        from repro.launch.mesh import make_host_mesh
        ckpt_mesh = make_host_mesh()

    start_step = 0
    params = opt_state = None
    if mgr is not None:
        restored = mgr.restore(mesh=ckpt_mesh)
        if restored is not None:
            params = restored["params"]
            opt_state = restored["opt"]
            start_step = restored["step"] + 1
            print(f"[train] resumed from step {restored['step']}")

    if params is None:
        params = T.init_model(jax.random.PRNGKey(args.seed), cfg)
        opt_state = adamw.init(params, ocfg)

    step_fn = jax.jit(S.make_train_step(cfg, ocfg, n_micro=args.n_micro))

    losses = []
    t0 = time.time()
    for step in range(start_step, args.steps):
        batch = data.batch_at(step)
        if cfg.family in ("vlm", "encdec"):
            extra_len = 8 if cfg.family == "vlm" else cfg.encoder_seq
            batch = dict(batch)
            batch["extra_embeds"] = jnp.zeros(
                (args.batch, extra_len, cfg.d_model), cfg.cdt)
            if cfg.family == "vlm":
                batch["labels"] = jnp.concatenate(
                    [jnp.full((args.batch, extra_len), -1, jnp.int32),
                     batch["labels"]], axis=1)
        params, opt_state, metrics = step_fn(params, opt_state, batch)
        losses.append(float(metrics["loss"]))
        if step % args.log_every == 0 or step == args.steps - 1:
            dt = (time.time() - t0) / max(len(losses), 1)
            print(f"[train] step={step} loss={losses[-1]:.4f} "
                  f"gnorm={float(metrics['grad_norm']):.3f} "
                  f"({dt*1000:.0f} ms/step)", flush=True)
        if mgr is not None and (step + 1) % args.ckpt_every == 0:
            mgr.save(step, params, opt_state, mesh=ckpt_mesh,
                     shard_count=args.ckpt_shards)
        if args.preempt_at is not None and step == args.preempt_at:
            print(f"[train] simulated preemption at step {step}")
            sys.exit(17)

    if mgr is not None:
        mgr.save(args.steps - 1, params, opt_state, mesh=ckpt_mesh,
                 shard_count=args.ckpt_shards)
    first, last = losses[0], sum(losses[-5:]) / min(len(losses), 5)
    print(f"[train] done: first loss {first:.4f} -> last(avg5) {last:.4f}")
    return first, last


if __name__ == "__main__":
    main()
