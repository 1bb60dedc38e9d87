"""Multi-head Latent Attention (DeepSeek-V3).

Queries and keys/values are projected through low-rank latents; only the
compressed latent c_kv (kv_lora_rank) plus the shared rotary key k_rope
(qk_rope_dim) are cached at decode time -- MLA *is* a learned KV-cache
compression, which interacts with this framework's error-bounded cache
compression (DESIGN.md §5: we optionally EB-compress the latent itself).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import layers as L
from repro.models.attention import blockwise_attn
from repro.models.config import ModelConfig


def init_mla(key, cfg: ModelConfig):
    d = cfg.d_model
    h = cfg.n_heads
    qr, kvr = cfg.q_lora_rank, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    ks = L.split_keys(key, 8)
    # q_lora_rank 0: queries projected directly (kimi-linear)
    q = ({"wdq": L.dense_init(ks[0], (d, qr), cfg.pdt),
          "q_norm": jnp.ones((qr,), cfg.pdt),
          "wuq": L.dense_init(ks[1], (qr, h, dn + dr), cfg.pdt)} if qr else
         {"wq": L.dense_init(ks[0], (d, h, dn + dr), cfg.pdt)})
    return {
        **q,
        "wdkv": L.dense_init(ks[2], (d, kvr + dr), cfg.pdt),
        "kv_norm": jnp.ones((kvr,), cfg.pdt),
        "wuk": L.dense_init(ks[3], (kvr, h, dn), cfg.pdt),
        "wuv": L.dense_init(ks[4], (kvr, h, dv), cfg.pdt),
        "wo": L.dense_init(ks[5], (h, dv, d), cfg.pdt),
    }


def _latents(x, p, cfg: ModelConfig, positions):
    """Project to q (B,S,H,dn+dr), c_kv (B,S,kvr), k_rope (B,S,1,dr).

    Under ``cfg.mla_nope`` the dr columns are kept but not rotated."""
    dn = cfg.qk_nope_dim
    if "wq" in p:
        q = jnp.einsum("bsd,dhe->bshe", x, p["wq"].astype(x.dtype))
    else:
        cq = L.rms_norm(x @ p["wdq"].astype(x.dtype), p["q_norm"])
        q = jnp.einsum("bsr,rhe->bshe", cq, p["wuq"].astype(x.dtype))
    ckv_full = x @ p["wdkv"].astype(x.dtype)
    c_kv = L.rms_norm(ckv_full[..., : cfg.kv_lora_rank], p["kv_norm"])
    k_rope = ckv_full[..., cfg.kv_lora_rank:][..., None, :]  # (B,S,1,dr)
    if not cfg.mla_nope:
        q_rope = L.apply_rope(q[..., dn:], positions, cfg.rope_theta)
        q = jnp.concatenate([q[..., :dn], q_rope], axis=-1)
        k_rope = L.apply_rope(k_rope, positions, cfg.rope_theta)
    return q, c_kv, k_rope


def _expand_kv(c_kv, k_rope, p, cfg: ModelConfig, dtype):
    """Latent -> full k (B,S,H,dn+dr) and v (B,S,H,dv)."""
    h = cfg.n_heads
    k_nope = jnp.einsum("bsr,rhe->bshe", c_kv, p["wuk"].astype(dtype))
    v = jnp.einsum("bsr,rhe->bshe", c_kv, p["wuv"].astype(dtype))
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope, k_nope.shape[:3] + (cfg.qk_rope_dim,))],
        axis=-1)
    return k, v


def mla_block(x, p, cfg: ModelConfig, positions):
    q, c_kv, k_rope = _latents(x, p, cfg, positions)
    k, v = _expand_kv(c_kv, k_rope, p, cfg, x.dtype)
    out = blockwise_attn(q, k, v, causal=True, chunk=cfg.attn_chunk)
    # v_head_dim may differ from qk dim; out is (B,S,H,dv)
    return jnp.einsum("bshe,hed->bsd", out, p["wo"].astype(x.dtype))


def mla_decode(x, p, cfg: ModelConfig, cache_latent, pos):
    """Decode step with the compressed latent cache (absorbed matmuls).

    cache_latent: (B, S, kvr + dr) storing [c_kv | k_rope].  The up-projection
    W_uk is absorbed into the query and W_uv into the output, so the latent
    cache is attended *directly* -- per-step FLOPs/bytes scale with kvr+dr,
    never with H * (dn + dv).  This is the production MLA decode identity:
      score = (q_nope W_uk) . c_kv + q_rope . k_rope
      out   = (attn @ c_kv) W_uv W_o
    """
    b = x.shape[0]
    dn, dr = cfg.qk_nope_dim, cfg.qk_rope_dim
    positions = jnp.full((b, 1), pos, jnp.int32)
    q, c_kv, k_rope = _latents(x, p, cfg, positions)
    entry = jnp.concatenate([c_kv, k_rope[:, :, 0, :]], axis=-1)
    cache_latent = jax.lax.dynamic_update_slice_in_dim(
        jnp.asarray(cache_latent), entry.astype(cache_latent.dtype), pos,
        axis=1)

    c_all = cache_latent[..., : cfg.kv_lora_rank].astype(x.dtype)   # (B,S,r)
    kr_all = cache_latent[..., cfg.kv_lora_rank:].astype(x.dtype)   # (B,S,dr)

    q_nope, q_rope = q[..., :dn], q[..., dn:]
    # keep the absorbed product in f32: a bf16 (B,1,H,kvr) intermediate
    # costs ~10% logit error vs the unabsorbed training path
    q_c = jnp.einsum("bqhn,rhn->bqhr", q_nope, p["wuk"],
                     preferred_element_type=jnp.float32)
    scores = (
        jnp.einsum("bqhr,bkr->bhqk", q_c, c_all.astype(jnp.float32))
        + jnp.einsum("bqhe,bke->bhqk", q_rope.astype(jnp.float32),
                     kr_all.astype(jnp.float32))
    ) * (dn + dr) ** -0.5
    valid = jnp.arange(cache_latent.shape[1]) <= pos
    scores = jnp.where(valid[None, None, None, :], scores, -1e30)
    a = jax.nn.softmax(scores, axis=-1)
    ctx = jnp.einsum("bhqk,bkr->bqhr", a,
                     c_all.astype(jnp.float32)).astype(x.dtype)
    v_ctx = jnp.einsum("bqhr,rhe->bqhe", ctx, p["wuv"].astype(x.dtype))
    return jnp.einsum("bshe,hed->bsd", v_ctx, p["wo"].astype(x.dtype)), \
        cache_latent
