"""Mixture-of-Experts block: shared + routed experts, top-k, capacity-based.

Dispatch is the sort-free capacity scheme: per-(token, expert) assignment
ranks are computed with an exclusive cumsum over the one-hot assignment
matrix; each expert keeps its first C tokens (GShard-style dropping).  The
(E, C, d) gather/scatter is what GSPMD turns into the EP all-to-all when
experts are sharded on the "model" axis (see runtime/sharding.py).

Aux load-balancing loss follows Switch: E * sum_e f_e * p_e.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import layers as L
from repro.models.config import ModelConfig


def init_moe(key, cfg: ModelConfig):
    d, de = cfg.d_model, cfg.d_expert
    e = cfg.n_experts
    ks = L.split_keys(key, 5)
    p = {
        "router": L.dense_init(ks[0], (d, e), jnp.float32),  # router in fp32
        "wg": L.dense_init(ks[1], (e, d, de), cfg.pdt),
        "wu": L.dense_init(ks[2], (e, d, de), cfg.pdt),
        "wd": L.dense_init(ks[3], (e, de, d), cfg.pdt),
    }
    if cfg.n_shared_experts:
        ds = de * cfg.n_shared_experts
        kss = L.split_keys(ks[4], 3)
        p["shared"] = {
            "wg": L.dense_init(kss[0], (d, ds), cfg.pdt),
            "wu": L.dense_init(kss[1], (d, ds), cfg.pdt),
            "wd": L.dense_init(kss[2], (ds, d), cfg.pdt),
        }
    return p


MOE_TOKEN_CHUNK = 65536


def moe_block(x, p, cfg: ModelConfig):
    """x: (B, S, d) -> (out (B,S,d), aux_loss scalar).

    Long-sequence calls (prefill_32k pushes 1M tokens through each layer)
    are scanned in MOE_TOKEN_CHUNK-token chunks: the (T, E) routing tensors
    and (E, C, d) dispatch buffers scale with the chunk, not the sequence.
    Capacity becomes per-chunk (GShard-style local capacity).
    """
    b, s, d = x.shape
    t = b * s
    xt = x.reshape(t, d)
    if t > MOE_TOKEN_CHUNK and t % MOE_TOKEN_CHUNK == 0:
        nc = t // MOE_TOKEN_CHUNK
        xc = xt.reshape(nc, MOE_TOKEN_CHUNK, d)

        def body(carry, xi):
            out, aux = _moe_tokens(xi, p, cfg)
            return carry + aux, out

        aux, outs = jax.lax.scan(body, jnp.float32(0), xc)
        return outs.reshape(b, s, d), aux / nc
    out, aux = _moe_tokens(xt, p, cfg)
    return out.reshape(b, s, d), aux


def _moe_tokens(xt, p, cfg: ModelConfig):
    """Dispatch/compute/combine for a flat (T, d) token block."""
    from repro.runtime import pspec

    t, d = xt.shape
    e, k = cfg.n_experts, cfg.top_k

    # Pin expert weights (and thereby their cotangents) to the configured
    # layout: the dispatch gather replicates its output, and without this
    # the stacked MoE weight *gradients* inherit that replication -- 2.6
    # TiB/device on deepseek-v3 (f32 grads of 58 x 3 x (256,7168,2048)).
    import os
    if os.environ.get("REPRO_MOE_SHARDING", "tp") == "ep":
        wg = pspec.shard(p["wg"], pspec.MODEL, pspec.BATCH, None)
        wu = pspec.shard(p["wu"], pspec.MODEL, pspec.BATCH, None)
        wd = pspec.shard(p["wd"], pspec.MODEL, None, pspec.BATCH)
    else:
        wg = pspec.shard(p["wg"], None, pspec.BATCH, pspec.MODEL)
        wu = pspec.shard(p["wu"], None, pspec.BATCH, pspec.MODEL)
        wd = pspec.shard(p["wd"], None, pspec.MODEL, pspec.BATCH)

    logits = xt.astype(jnp.float32) @ p["router"]
    logits = pspec.shard(logits, pspec.BATCH, None)
    gates = (jax.nn.sigmoid(logits) if cfg.router_act == "sigmoid"
             else jax.nn.softmax(logits, axis=-1))           # (T, E)
    topv, topi = jax.lax.top_k(gates, k)                     # (T, k)
    topv = topv / jnp.maximum(topv.sum(-1, keepdims=True), 1e-9) \
        * cfg.routed_scale

    # Capacity + ranks.  assign: (T, E) in {0,1}; rank = exclusive cumsum.
    cap = int(max(1, round(t * k / e * cfg.capacity_factor)))
    assign = jnp.zeros((t, e), jnp.int32)
    assign = assign.at[jnp.arange(t)[:, None], topi].set(1)
    ranks = jnp.cumsum(assign, axis=0) - assign              # (T, E)

    # Token ids routed to each (expert, slot); empty slots -> t (dropped row).
    rk = jnp.take_along_axis(ranks, topi, axis=1)            # (T, k)
    keep = rk < cap
    ek_safe = jnp.where(keep, topi, e)                       # e => OOB, dropped
    tok_ids = jnp.full((e, cap), t, jnp.int32)
    tok_ids = tok_ids.at[ek_safe, jnp.clip(rk, 0, cap - 1)].set(
        jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32)[:, None], (t, k)),
        mode="drop")

    xe = jnp.concatenate([xt, jnp.zeros((1, d), xt.dtype)], 0)[tok_ids]
    # (E, C, d) expert GEMMs
    h = L.act_fn(cfg.act)(
        jnp.einsum("ecd,edf->ecf", xe, wg.astype(xe.dtype))) * \
        jnp.einsum("ecd,edf->ecf", xe, wu.astype(xe.dtype))
    ye = jnp.einsum("ecf,efd->ecd", h, wd.astype(xe.dtype))

    # Combine: scatter expert outputs back with gate weights.
    gate_at = jnp.zeros((t, e), jnp.float32)
    gate_at = gate_at.at[jnp.arange(t)[:, None], topi].set(topv)
    w = gate_at[jnp.clip(tok_ids, 0, t - 1),
                jnp.arange(e)[:, None]] * (tok_ids < t)
    out = jnp.zeros((t + 1, d), jnp.float32)
    out = out.at[tok_ids.reshape(-1)].add(
        (ye * w[..., None].astype(ye.dtype)).reshape(-1, d).astype(jnp.float32),
        mode="drop")
    out = out[:t].astype(xt.dtype)

    if cfg.n_shared_experts:
        out = out + L.gated_mlp(xt, p["shared"], cfg.act)

    # Switch aux loss.
    frac_tokens = jnp.mean(assign.astype(jnp.float32), axis=0)
    frac_probs = jnp.mean(gates, axis=0)
    aux = e * jnp.sum(frac_tokens * frac_probs)
    return out, aux
