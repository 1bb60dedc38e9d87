"""Serving driver: batched autoregressive decode with optional compressed
KV-cache offload (the paper's in-memory compression use case made live).

Flow: prompt prefill (decode steps over the prompt) -> optionally compress
the prompt-phase cache with the SZ pipeline and restore it through the
optimized parallel Huffman decoder -> continue decoding.  Reports tokens/s,
cache compression ratio, and the decode-path error introduced.

``--kv-offload`` instead pages the prompt KV blocks *through the
compressed tensor store*: prefix blocks are evicted to ``.szt`` archives
(``repro.store.KVPager``) and demand-paged back before generation; repeat
page-ins of a block hit the plan cache, so steady-state paging is pure
phase-4 decode.  Page-in decodes all blocks in one class-merged dispatch
set; with ``--concurrency N`` the blocks are instead requested by N
concurrent decode streams through one shared ``repro.serving``
scheduler -- their requests coalesce within ``--batch-window`` and the
shared prefix decodes exactly once.

Usage:
  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-0.6b --reduced \
      --batch 4 --prompt-len 32 --gen-len 32 --compress-kv --kv-eb 1e-3
  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-0.6b --reduced \
      --kv-offload --kv-block 16 --kv-offload-dir /tmp/kv_blocks
  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-0.6b --reduced \
      --kv-offload --concurrency 8 --batch-window 0.002
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.models import decode as D
from repro.models import kvcache
from repro.models import steps as S
from repro.models import transformer as T
from repro.runtime.compile_cache import enable_compile_cache


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen-len", type=int, default=32)
    ap.add_argument("--kv-len", type=int, default=None)
    ap.add_argument("--compress-kv", action="store_true")
    ap.add_argument("--kv-eb", type=float, default=None,
                    help="relative error bound for KV compression "
                         "(default: the CodecConfig default)")
    ap.add_argument("--kv-backend", default=None,
                    help="decode backend for KV restore ('ref', 'pallas'; "
                         "default: the CodecConfig default)")
    ap.add_argument("--kv-encode-backend", default=None,
                    help="encode backend for KV eviction/compression "
                         "('ref' host path, 'jnp'/'pallas' device write "
                         "path; default: the CodecConfig default)")
    ap.add_argument("--kv-offload", action="store_true",
                    help="page prompt KV blocks out to store archives and "
                         "demand-page them back before generation")
    ap.add_argument("--kv-block", type=int, default=16,
                    help="tokens per offloaded KV block")
    ap.add_argument("--kv-offload-dir", default=None,
                    help="directory for KV block archives "
                         "(default: a temp dir)")
    ap.add_argument("--concurrency", type=int, default=1,
                    help="with --kv-offload: number of concurrent decode "
                         "streams paging the prompt blocks back through one "
                         "shared serving scheduler (they share the hot "
                         "prefix, so its blocks decode once; 1 = direct "
                         "batched page-in, no scheduler)")
    ap.add_argument("--batch-window", type=float, default=0.002,
                    help="scheduler batching window in seconds: page-in "
                         "requests arriving within one window coalesce "
                         "into one class-merged decode dispatch set")
    ap.add_argument("--kv-recovery", default="raise",
                    choices=["raise", "skip", "zero_fill"],
                    help="recovery policy for lost/corrupt KV blocks: "
                         "'raise' aborts on PageLostError; 'skip'/"
                         "'zero_fill' keep serving degraded -- the lost "
                         "block's span stays zeroed and "
                         "stats['pages_lost'] counts it")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = configs.get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    kv_len = args.kv_len or (args.prompt_len + args.gen_len)

    # One configured codec drives every KV compression path of this run
    # (offload paging AND in-memory compress/restore): eb, bound mode,
    # decode method/backend, and the plan cache travel together.
    from repro.core import Codec, CodecConfig
    overrides = {k: v for k, v in (("eb", args.kv_eb),
                                   ("backend", args.kv_backend),
                                   ("encode_backend",
                                    args.kv_encode_backend),
                                   ("recovery", args.kv_recovery))
                 if v is not None}
    kv_codec = Codec(CodecConfig(**overrides))

    key = jax.random.PRNGKey(args.seed)
    params = T.init_model(key, cfg)
    serve = jax.jit(S.make_serve_step(cfg), static_argnums=())
    prompt = jax.random.randint(key, (args.batch, args.prompt_len), 0,
                                cfg.vocab)

    cache = D.init_cache(cfg, args.batch, kv_len)
    if cfg.family == "encdec":
        # cross-attention K/V from the (stubbed) encoder features
        enc = jnp.zeros((args.batch, cfg.encoder_seq, cfg.d_model), cfg.cdt)
        from repro.models import attention as A
        import jax.numpy as _j
        xk = []
        xv = []
        lp = params["layers"]
        for li in range(cfg.n_layers):
            layer = jax.tree.map(lambda x: x[li], lp)
            k = jnp.einsum("bsd,dhe->bshe", enc,
                           layer["xattn"]["wk"].astype(enc.dtype))
            v = jnp.einsum("bsd,dhe->bshe", enc,
                           layer["xattn"]["wv"].astype(enc.dtype))
            xk.append(k)
            xv.append(v)
        cache["xk"] = jnp.stack(xk)
        cache["xv"] = jnp.stack(xv)

    # --- prefill by stepping the decoder over the prompt ------------------
    t0 = time.time()
    logits = None
    for t in range(args.prompt_len):
        logits, cache = serve(params, prompt[:, t:t + 1], cache, jnp.int32(t))
    t_prefill = time.time() - t0

    # --- optional KV paging through the compressed tensor store -----------
    ratio = None
    kv_err = 0.0
    page_stats = None
    if args.kv_offload:
        import tempfile

        from repro.models.kvcache import (KVPager, offload_prefix,
                                          page_in_blocks_batched)
        from repro.store import PageLostError

        # The cache layout decides what is paged by token ranges: tensors
        # without a token axis (recurrent states, cross-attention k/v) are
        # never sliced.
        offload_dir = args.kv_offload_dir or tempfile.mkdtemp(
            prefix="kv_blocks_")
        pager = KVPager(offload_dir, codec=kv_codec,
                        layout=D.cache_layout(cfg))
        keys = pager.pageable(cache, args.prompt_len)
        snapshot = {k: np.asarray(cache[k], np.float32) for k in keys}
        t0 = time.time()
        cache, block_ids = offload_prefix(cache, pager, args.prompt_len,
                                          block_tokens=args.kv_block,
                                          keys=keys)
        t_out = time.time() - t0
        t0 = time.time()
        # Under a non-raise recovery policy a lost block (missing/corrupt
        # archive -> PageLostError, counted in stats["pages_lost"]) keeps
        # its span zeroed and serving continues degraded.
        lost: list = []
        on_lost = (None if args.kv_recovery == "raise"
                   else lambda bid, e: lost.append((bid, e)))
        sched_stats = None
        if args.concurrency > 1:
            # N concurrent decode streams page the same prompt blocks
            # through one shared scheduler: their requests coalesce into
            # class-merged ticks and each distinct block decodes once
            # (lane 0's results are installed into this process' cache).
            import threading

            from repro.serving import DecodeScheduler

            results: dict = {}
            errors: list = []
            with DecodeScheduler(pager,
                                 batch_window_s=args.batch_window) as sched:
                def lane(lane_id: int):
                    futs = [(bid, sched.submit(lane_id, bid))
                            for bid in block_ids]
                    for bid, f in futs:
                        try:
                            tensors = f.result()
                            if lane_id == 0:
                                results[bid] = tensors
                        except PageLostError as e:
                            if lane_id != 0:
                                continue
                            if on_lost is None:
                                errors.append(e)
                            else:
                                lost.append((bid, e))

                lanes = [threading.Thread(target=lane, args=(i,))
                         for i in range(args.concurrency)]
                for th in lanes:
                    th.start()
                for th in lanes:
                    th.join()
                sched_stats = dict(sched.stats)
            if errors:
                raise errors[0]
            for bid, tensors in results.items():
                cache = pager.install(cache, bid, tensors)
        else:
            cache = page_in_blocks_batched(cache, pager, block_ids,
                                           on_lost=on_lost)
        t_in = time.time() - t0
        lost_ids = {bid for bid, _ in lost}
        paged = set()
        for bid in block_ids:
            if bid not in lost_ids:
                paged |= set(pager.block_meta(bid)["names"])
        for name in paged:
            kv_err = max(kv_err, float(np.max(np.abs(
                np.asarray(cache[name], np.float32) - snapshot[name]))))
        ratio = pager.ratio
        page_stats = dict(pager.stats)
        if sched_stats is not None:
            page_stats["scheduler"] = sched_stats
        page_stats["encode_dispatches"] = kv_codec.stats["encode_dispatches"]
        page_stats["encode_fallbacks"] = kv_codec.stats["encode_fallbacks"]
        print(f"[serve] kv offload: {len(block_ids)} blocks x "
              f"{args.kv_block} toks -> {offload_dir} "
              f"({pager.stats['bytes_raw']/2**20:.2f} MiB raw, "
              f"{pager.stats['bytes_compressed']/2**20:.2f} MiB stored, "
              f"ratio {ratio:.2f}x); page-out {t_out:.2f}s, "
              f"page-in {t_in:.2f}s, max err {kv_err:.2e}")
        if sched_stats is not None:
            print(f"[serve] kv scheduler: {args.concurrency} streams x "
                  f"{len(block_ids)} blocks = {sched_stats['requests']} "
                  f"requests -> {sched_stats['batch_dispatches']} batched "
                  f"dispatches ({sched_stats['blocks_decoded']} blocks "
                  f"decoded once; prefix_hits="
                  f"{sched_stats['prefix_hits']}, coalesced="
                  f"{sched_stats['coalesced_requests']})")
        if lost:
            print(f"[serve] kv paging DEGRADED: {len(lost)} block(s) lost "
                  f"(pages_lost={pager.stats['pages_lost']}); their token "
                  f"spans stay zeroed")

    # --- optional cache compress/restore round trip ------------------------
    if args.compress_kv:
        skip = tuple(k for k in cache if k in ("xk", "xv"))
        cc = kvcache.compress_cache(
            {k: v for k, v in cache.items()}, codec=kv_codec, skip=skip)
        restored = kvcache.decompress_cache(cc, codec=kv_codec)
        for name, arr in restored.items():
            kv_err = max(kv_err, float(np.max(np.abs(
                np.asarray(arr, np.float32)
                - np.asarray(cache[name], np.float32)))))
            cache[name] = arr
        ratio = cc.ratio
        print(f"[serve] kv cache compressed {cc.original_bytes/2**20:.1f} MiB"
              f" -> {cc.compressed_bytes/2**20:.1f} MiB "
              f"(ratio {ratio:.2f}x, max err {kv_err:.2e})")

    # --- generation ---------------------------------------------------------
    tok = jnp.argmax(logits[:, -1], axis=-1)[:, None].astype(jnp.int32)
    out_tokens = [tok]
    t0 = time.time()
    for t in range(args.prompt_len, args.prompt_len + args.gen_len):
        logits, cache = serve(params, tok, cache, jnp.int32(t))
        tok = jnp.argmax(logits[:, -1], axis=-1)[:, None].astype(jnp.int32)
        out_tokens.append(tok)
    t_gen = time.time() - t0
    toks = args.batch * args.gen_len
    print(f"[serve] prefill {args.prompt_len} toks in {t_prefill:.2f}s; "
          f"generated {toks} tokens in {t_gen:.2f}s "
          f"({toks / max(t_gen, 1e-9):.1f} tok/s)")
    return {"ratio": ratio, "kv_err": kv_err, "page_stats": page_stats,
            "tokens": np.asarray(jnp.concatenate(out_tokens, axis=1))}


if __name__ == "__main__":
    main()
