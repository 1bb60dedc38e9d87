"""Kimi Linear: Kimi Delta Attention (KDA) layers beside MLA layers.

KDA (Moonshot AI, "Kimi Linear: An Expressive, Efficient Attention
Architecture", 2025) is a gated delta-rule linear attention with a
channel-wise decay.  Per head, with q and k L2-normalised over their dk
channels:

  S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T
  o_t = S_t^T q_t / sqrt(dk)

``S`` is a (dk, dv) float32 matrix per head and sequence: the decode
cache's ``kda_state``.  ``alpha_t = exp(-exp(A) * softplus(x W_fa W_fb +
dt_bias))`` lies in (0, 1)^dk; ``beta_t = sigmoid(x W_b)`` is one number
per head.  q, k and v each pass a causal depthwise convolution of
``kda_conv`` taps and a SiLU first; the cache's ``kda_conv`` holds the last
``kda_conv - 1`` inputs of the three, channels (q | k | v) on axis 2.  The
output is ``rms_norm(o) * sigmoid(x W_ga W_gb)`` per head, then ``W_o``.

The model's layers are KDA or MLA as ``cfg.kda_layers`` and
``cfg.mla_layers`` (1-indexed) say; the feed-forward is dense for the first
``first_k_dense`` layers and MoE after.  Training and decode run the same
exact per-step scan over the sequence (as ``rwkv.py`` does).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import layers as L
from repro.models import moe as MOE
from repro.models.config import ModelConfig


def kda_dims(cfg: ModelConfig):
    return cfg.kda_heads, cfg.kda_head_dim


def layer_order(cfg: ModelConfig) -> list:
    """``(kind, j)`` for each layer in order: ``"kda"`` or ``"mla"``, and
    the layer's index in the stack (and cache tensor) of its kind."""
    kda, mla = sorted(cfg.kda_layers), sorted(cfg.mla_layers)
    if sorted(kda + mla) != list(range(1, cfg.n_layers + 1)):
        raise ValueError(f"{cfg.name}: kda_layers {kda} and mla_layers "
                         f"{mla} do not cover layers 1..{cfg.n_layers}")
    return [("kda", kda.index(i)) if i in kda else ("mla", mla.index(i))
            for i in range(1, cfg.n_layers + 1)]


def init_kda(key, cfg: ModelConfig):
    d = cfg.d_model
    h, dk = kda_dims(cfg)
    c = h * dk
    ks = L.split_keys(key, 10)
    return {
        "wq": L.dense_init(ks[0], (d, c), cfg.pdt),
        "wk": L.dense_init(ks[1], (d, c), cfg.pdt),
        "wv": L.dense_init(ks[2], (d, c), cfg.pdt),
        "conv": L.dense_init(ks[3], (cfg.kda_conv, 3 * c), cfg.pdt,
                             scale=0.5),
        "wfa": L.dense_init(ks[4], (d, dk), cfg.pdt),
        "wfb": L.dense_init(ks[5], (dk, c), cfg.pdt),
        "a_log": jnp.zeros((h,), jnp.float32),
        "dt_bias": jnp.zeros((c,), jnp.float32),
        "wb": L.dense_init(ks[6], (d, h), cfg.pdt),
        "wga": L.dense_init(ks[7], (d, dk), cfg.pdt),
        "wgb": L.dense_init(ks[8], (dk, c), cfg.pdt),
        "o_norm": jnp.ones((dk,), cfg.pdt),
        "wo": L.dense_init(ks[9], (c, d), cfg.pdt),
    }


def short_conv(u, window, w):
    """Causal depthwise convolution over time, then SiLU.

    u: (B, S, C); window: (B, C, K-1), the K-1 inputs before ``u[:, 0]``;
    w: (K, C).  Returns (y (B, S, C), the window after ``u``)."""
    k, s = w.shape[0], u.shape[1]
    full = jnp.concatenate([jnp.swapaxes(window, 1, 2).astype(u.dtype), u],
                           axis=1)
    y = sum(full[:, i:i + s] * w[i].astype(u.dtype) for i in range(k))
    return jax.nn.silu(y), jnp.swapaxes(full[:, s:], 1, 2)


def _l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def kda_block(x, p, cfg: ModelConfig, state, window):
    """x: (B, S, d); state: (B, H, dk, dk) f32; window: (B, 3 H dk, K-1).

    Returns (y (B, S, d), state, window) after the S steps."""
    b, s, _ = x.shape
    h, dk = kda_dims(cfg)
    c = h * dk
    f32 = jnp.float32
    qkv = jnp.concatenate([x @ p[n].astype(x.dtype)
                           for n in ("wq", "wk", "wv")], axis=-1)
    qkv, window = short_conv(qkv, window, p["conv"])
    q, k, v = (qkv[..., i * c:(i + 1) * c].reshape(b, s, h, dk).astype(f32)
               for i in range(3))
    q = _l2norm(q) * dk ** -0.5
    k = _l2norm(k)
    g = ((x @ p["wfa"].astype(x.dtype)) @ p["wfb"].astype(x.dtype)).astype(
        f32) + p["dt_bias"]
    alpha = jnp.exp(-jnp.exp(p["a_log"])[:, None]
                    * jax.nn.softplus(g.reshape(b, s, h, dk)))
    beta = jax.nn.sigmoid((x @ p["wb"].astype(x.dtype)).astype(f32))

    def step(st, inp):
        qt, kt, vt, at, bt = inp                       # (B,H,dk), beta (B,H)
        st = at[..., :, None] * st
        err = vt - jnp.einsum("bhk,bhkv->bhv", kt, st)
        st = st + bt[..., None, None] * kt[..., :, None] * err[..., None, :]
        return st, jnp.einsum("bhk,bhkv->bhv", qt, st)

    seq = tuple(jnp.moveaxis(a, 1, 0) for a in (q, k, v, alpha, beta))
    state, o = jax.lax.scan(step, state, seq)
    o = jnp.moveaxis(o, 0, 1).astype(x.dtype)          # (B,S,H,dk)
    gate = (x @ p["wga"].astype(x.dtype)) @ p["wgb"].astype(x.dtype)
    o = L.rms_norm(o, p["o_norm"]) * jax.nn.sigmoid(gate.reshape(b, s, h, dk))
    return o.reshape(b, s, c) @ p["wo"].astype(x.dtype), state, window


def ffn(x, params, cfg: ModelConfig, i: int):
    """Layer ``i``'s feed-forward with its residual: (x, router aux)."""
    nk = cfg.first_k_dense
    if i < nk:
        lp = jax.tree.map(lambda a: a[i], params["dense_layers"])
        return x + L.mlp_apply(L.rms_norm(x, lp["ln2"]), lp["mlp"],
                               cfg.act), jnp.float32(0)
    lp = jax.tree.map(lambda a: a[i - nk], params["layers"])
    mo, aux = MOE.moe_block(L.rms_norm(x, lp["ln2"]), lp["moe"], cfg)
    return x + mo, aux
