"""Model assembly for every assigned architecture family.

All stacks scan over layers with stacked parameters (compile-time O(1) in
depth, FSDP all-gathers overlap with layer compute under XLA latency hiding)
and wrap the layer body in ``jax.checkpoint`` when cfg.remat.

Families:
  dense / vlm  -- GQA attention (+SWA/qk-norm/bias/M-RoPE) + gated MLP
  moe          -- GQA attention + shared/routed top-k MoE
  mla_moe      -- MLA attention + MoE (+ optional MTP head), DeepSeek-V3
  kda_mla      -- KDA linear-attention and MLA layers + MoE, Kimi Linear
                  (layers unrolled in the config's order, ``models/kda.py``)
  hybrid_ssm   -- Mamba2 blocks + weight-shared attention block every k
  rwkv         -- RWKV6 time-mix + channel-mix
  encdec       -- Whisper: bidirectional encoder over stubbed frames +
                  causal decoder with cross-attention
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.models import attention as A
from repro.models import kda as KDA
from repro.models import layers as L
from repro.models import mla as MLA
from repro.models import moe as MOE
from repro.models import rwkv as RWKV
from repro.models import ssm as SSM
from repro.models.config import ModelConfig
from repro.runtime import pspec


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------


def _init_mlp(key, cfg: ModelConfig, d_ff=None):
    d = cfg.d_model
    ff = d_ff or cfg.d_ff
    ks = L.split_keys(key, 3)
    if cfg.mlp_type == "plain":
        return {
            "wi": L.dense_init(ks[0], (d, ff), cfg.pdt),
            "wo": L.dense_init(ks[1], (ff, d), cfg.pdt),
        }
    return {
        "wg": L.dense_init(ks[0], (d, ff), cfg.pdt),
        "wu": L.dense_init(ks[1], (d, ff), cfg.pdt),
        "wd": L.dense_init(ks[2], (ff, d), cfg.pdt),
    }


def _init_dense_layer(key, cfg: ModelConfig, cross: bool = False):
    ks = L.split_keys(key, 4)
    p = {
        "ln1": jnp.ones((cfg.d_model,), cfg.pdt),
        "attn": A.init_attn(ks[0], cfg),
        "ln2": jnp.ones((cfg.d_model,), cfg.pdt),
        "mlp": _init_mlp(ks[1], cfg),
    }
    if cross:
        p["ln_x"] = jnp.ones((cfg.d_model,), cfg.pdt)
        p["xattn"] = A.init_attn(ks[2], cfg)
    return p


def _init_moe_layer(key, cfg: ModelConfig, use_mla: bool):
    ks = L.split_keys(key, 3)
    return {
        "ln1": jnp.ones((cfg.d_model,), cfg.pdt),
        "attn": MLA.init_mla(ks[0], cfg) if use_mla else A.init_attn(ks[0], cfg),
        "ln2": jnp.ones((cfg.d_model,), cfg.pdt),
        "moe": MOE.init_moe(ks[1], cfg),
    }


def _init_ssm_layer(key, cfg: ModelConfig):
    # Mamba2 layers carry no separate MLP (Zamba2: the d_ff MLP lives in the
    # weight-shared attention block only).
    ks = L.split_keys(key, 2)
    return {
        "ln1": jnp.ones((cfg.d_model,), cfg.pdt),
        "ssm": SSM.init_ssm(ks[0], cfg),
    }


def _init_rwkv_layer(key, cfg: ModelConfig):
    ks = L.split_keys(key, 2)
    return {
        "ln1": jnp.ones((cfg.d_model,), cfg.pdt),
        "tmix": RWKV.init_time_mix(ks[0], cfg),
        "ln2": jnp.ones((cfg.d_model,), cfg.pdt),
        "cmix": RWKV.init_channel_mix(ks[1], cfg),
    }


def _stack_init(layer_init, key, n, *args):
    keys = jax.random.split(key, n)
    return jax.vmap(lambda k: layer_init(k, *args))(keys)


def init_model(key, cfg: ModelConfig):
    ks = L.split_keys(key, 8)
    d = cfg.d_model
    params = {
        "embed": L.dense_init(ks[0], (cfg.vocab, d), cfg.pdt, scale=0.02),
        "final_norm": jnp.ones((d,), cfg.pdt),
    }
    if not cfg.tie_embeddings:
        params["unembed"] = L.dense_init(ks[1], (cfg.vocab, d), cfg.pdt,
                                         scale=0.02)

    fam = cfg.family
    if fam in ("dense", "vlm"):
        params["layers"] = _stack_init(_init_dense_layer, ks[2],
                                       cfg.n_layers, cfg)
    elif fam in ("moe", "mla_moe"):
        use_mla = fam == "mla_moe"
        nk = cfg.first_k_dense

        def _init_prefix_layer(k):
            # DeepSeek-V3: every layer uses MLA attention; only the FFN of
            # the first_k_dense layers is dense instead of MoE.
            kk = L.split_keys(k, 2)
            return {
                "ln1": jnp.ones((cfg.d_model,), cfg.pdt),
                "attn": MLA.init_mla(kk[0], cfg) if use_mla
                else A.init_attn(kk[0], cfg),
                "ln2": jnp.ones((cfg.d_model,), cfg.pdt),
                "mlp": _init_mlp(kk[1], cfg),
            }

        if nk:
            params["dense_layers"] = _stack_init(_init_prefix_layer, ks[3], nk)
        params["layers"] = _stack_init(
            lambda k: _init_moe_layer(k, cfg, use_mla), ks[2],
            cfg.n_layers - nk)
        if cfg.mtp:
            kk = L.split_keys(ks[4], 2)
            params["mtp"] = {
                "fuse": L.dense_init(kk[0], (2 * d, d), cfg.pdt),
                "block": _init_dense_layer(kk[1], cfg),
                "norm": jnp.ones((d,), cfg.pdt),
            }
    elif fam == "kda_mla":
        ones = jnp.ones((d,), cfg.pdt)
        params["kda"] = _stack_init(
            lambda k: {"ln1": ones, "kda": KDA.init_kda(k, cfg)}, ks[2],
            len(cfg.kda_layers))
        params["mla"] = _stack_init(
            lambda k: {"ln1": ones, "attn": MLA.init_mla(k, cfg)}, ks[3],
            len(cfg.mla_layers))
        if cfg.first_k_dense:
            params["dense_layers"] = _stack_init(
                lambda k: {"ln2": ones, "mlp": _init_mlp(k, cfg)}, ks[4],
                cfg.first_k_dense)
        params["layers"] = _stack_init(
            lambda k: {"ln2": ones, "moe": MOE.init_moe(k, cfg)}, ks[5],
            cfg.n_layers - cfg.first_k_dense)
    elif fam == "hybrid_ssm":
        params["layers"] = _stack_init(_init_ssm_layer, ks[2],
                                       cfg.n_layers, cfg)
        params["shared_attn"] = _init_dense_layer(ks[3], cfg)
    elif fam == "rwkv":
        params["layers"] = _stack_init(_init_rwkv_layer, ks[2],
                                       cfg.n_layers, cfg)
    elif fam == "encdec":
        params["encoder"] = _stack_init(_init_dense_layer, ks[3],
                                        cfg.encoder_layers, cfg)
        params["enc_norm"] = jnp.ones((d,), cfg.pdt)
        params["layers"] = _stack_init(
            lambda k: _init_dense_layer(k, cfg, cross=True), ks[2],
            cfg.n_layers)
    else:
        raise ValueError(fam)
    return params


# ---------------------------------------------------------------------------
# Layer bodies (training / prefill)
# ---------------------------------------------------------------------------


def _dense_layer(x, p, cfg: ModelConfig, positions, *, causal=True,
                 enc_out=None, window=None):
    h = A.attn_block(L.rms_norm(x, p["ln1"]), p["attn"], cfg, positions,
                     causal=causal, window=window)
    x = x + h
    if enc_out is not None:
        h = A.attn_block(L.rms_norm(x, p["ln_x"]), p["xattn"], cfg,
                         None, causal=False, kv_x=enc_out)
        x = x + h
    x = x + L.mlp_apply(L.rms_norm(x, p["ln2"]), p["mlp"], cfg.act)
    # Sequence-sharded layer boundary: the remat-saved per-layer stack
    # inherits this spec, cutting saved-activation HBM by the model-axis
    # degree (Megatron-SP layout between layers).
    return pspec.shard(x, pspec.BATCH, pspec.MODEL, None)


def _moe_layer(x, p, cfg: ModelConfig, positions, use_mla: bool):
    xn = L.rms_norm(x, p["ln1"])
    h = MLA.mla_block(xn, p["attn"], cfg, positions) if use_mla else \
        A.attn_block(xn, p["attn"], cfg, positions)
    x = x + h
    mo, aux = MOE.moe_block(L.rms_norm(x, p["ln2"]), p["moe"], cfg)
    return pspec.shard(x + mo, pspec.BATCH, pspec.MODEL, None), aux


def _ssm_layer(x, p, cfg: ModelConfig):
    x = x + SSM.ssm_block(L.rms_norm(x, p["ln1"]), p["ssm"], cfg)
    return pspec.shard(x, pspec.BATCH, pspec.MODEL, None)


def _rwkv_layer(x, p, cfg: ModelConfig):
    bsz = x.shape[0]
    h, dh = RWKV.rwkv_dims(cfg)
    zero_prev = jnp.zeros((bsz, cfg.d_model), x.dtype)
    state0 = jnp.zeros((bsz, h, dh, dh), jnp.float32)
    y, _, _ = RWKV.time_mix(L.rms_norm(x, p["ln1"]), zero_prev, state0,
                            p["tmix"], cfg)
    x = x + y
    y, _ = RWKV.channel_mix(L.rms_norm(x, p["ln2"]), zero_prev, p["cmix"], cfg)
    return pspec.shard(x + y, pspec.BATCH, pspec.MODEL, None)


# ---------------------------------------------------------------------------
# Forward (training / prefill): tokens -> logits
# ---------------------------------------------------------------------------


def _positions(cfg: ModelConfig, bsz: int, s: int):
    # Batch-free position vectors: identical across the batch at train /
    # prefill time, so keeping them (S,)-shaped keeps the rope sin/cos
    # tables tiny and replication-safe under GSPMD.
    pos = jnp.arange(s, dtype=jnp.int32)
    if cfg.mrope:
        # Text tokens: all three M-RoPE streams coincide (DESIGN.md §5);
        # the vision stub supplies patch embeddings with text-linear ids.
        return jnp.broadcast_to(pos[None, :], (3, s))
    return pos


def _maybe_remat(f, cfg: ModelConfig):
    return jax.checkpoint(f) if cfg.remat else f


def forward_hidden(params, tokens, cfg: ModelConfig, extra_embeds=None):
    """tokens: (B, S) int32; extra_embeds: (B, P, d) modality-stub embeddings
    prepended to the token embeddings (vlm patches / audio frames).

    Returns (hidden (B, S_total, d) post-final-norm, aux_loss scalar).
    """
    x = params["embed"][tokens].astype(cfg.cdt)
    if extra_embeds is not None:
        x = jnp.concatenate([extra_embeds.astype(cfg.cdt), x], axis=1)
    x = pspec.shard(x, pspec.BATCH, None, None)
    bsz, s, _ = x.shape
    positions = _positions(cfg, bsz, s)
    aux_total = jnp.float32(0)

    fam = cfg.family
    if fam in ("dense", "vlm"):
        def body(x, lp):
            return _dense_layer(x, lp, cfg, positions), None
        x, _ = jax.lax.scan(_maybe_remat(body, cfg), x, params["layers"])

    elif fam in ("moe", "mla_moe"):
        use_mla = fam == "mla_moe"
        if "dense_layers" in params:
            def dbody(x, lp):
                xn = L.rms_norm(x, lp["ln1"])
                h = MLA.mla_block(xn, lp["attn"], cfg, positions) if use_mla \
                    else A.attn_block(xn, lp["attn"], cfg, positions)
                x = x + h
                x = x + L.mlp_apply(L.rms_norm(x, lp["ln2"]), lp["mlp"],
                                    cfg.act)
                return x, None
            x, _ = jax.lax.scan(_maybe_remat(dbody, cfg), x,
                                params["dense_layers"])

        def mbody(x, lp):
            x, aux = _moe_layer(x, lp, cfg, positions, use_mla)
            return x, aux
        x, auxs = jax.lax.scan(_maybe_remat(mbody, cfg), x, params["layers"])
        aux_total = aux_total + jnp.sum(auxs) * cfg.router_aux_coef

    elif fam == "kda_mla":
        h, dk = KDA.kda_dims(cfg)
        state0 = jnp.zeros((bsz, h, dk, dk), jnp.float32)
        window0 = jnp.zeros((bsz, 3 * h * dk, cfg.kda_conv - 1), x.dtype)
        for i, (kind, j) in enumerate(KDA.layer_order(cfg)):
            lp = jax.tree.map(lambda a: a[j], params[kind])
            xn = L.rms_norm(x, lp["ln1"])
            if kind == "kda":
                y, _, _ = KDA.kda_block(xn, lp["kda"], cfg, state0, window0)
            else:
                y = MLA.mla_block(xn, lp["attn"], cfg, positions)
            x, aux = KDA.ffn(x + y, params, cfg, i)
            aux_total = aux_total + aux * cfg.router_aux_coef

    elif fam == "hybrid_ssm":
        every = max(cfg.hybrid_attn_every, 1)
        shared = params["shared_attn"]

        def body(carry, inp):
            x, idx = carry
            x = _ssm_layer(x, inp, cfg)
            use_attn = (idx % every) == (every - 1)
            x = jax.lax.cond(
                use_attn,
                lambda x: _dense_layer(x, shared, cfg, positions),
                lambda x: x,
                x)
            return (x, idx + 1), None
        (x, _), _ = jax.lax.scan(_maybe_remat(body, cfg), (x, jnp.int32(0)),
                                 params["layers"])

    elif fam == "rwkv":
        def body(x, lp):
            return _rwkv_layer(x, lp, cfg), None
        x, _ = jax.lax.scan(_maybe_remat(body, cfg), x, params["layers"])

    elif fam == "encdec":
        assert extra_embeds is not None, "encdec needs stub frame embeddings"
        enc = extra_embeds.astype(cfg.cdt)
        enc_pos = _positions(cfg, enc.shape[0], enc.shape[1])

        def ebody(h, lp):
            return _dense_layer(h, lp, cfg, enc_pos, causal=False), None
        enc, _ = jax.lax.scan(_maybe_remat(ebody, cfg), enc,
                              params["encoder"])
        enc = L.rms_norm(enc, params["enc_norm"])

        x = params["embed"][tokens].astype(cfg.cdt)
        dec_pos = _positions(cfg, bsz, tokens.shape[1])

        def dbody(h, lp):
            return _dense_layer(h, lp, cfg, dec_pos, enc_out=enc), None
        x, _ = jax.lax.scan(_maybe_remat(dbody, cfg), x, params["layers"])
    else:
        raise ValueError(fam)

    x = L.rms_norm(x, params["final_norm"])
    return x, aux_total


def forward(params, tokens, cfg: ModelConfig, extra_embeds=None):
    """Full forward: (logits (B, S_total, V), aux)."""
    x, aux = forward_hidden(params, tokens, cfg, extra_embeds=extra_embeds)
    unembed = params.get("unembed", params["embed"])
    logits = jnp.einsum("bsd,vd->bsv", x, unembed.astype(x.dtype))
    return logits, aux


def mtp_logits(params, tokens, hidden, cfg: ModelConfig):
    """DeepSeek-V3 multi-token prediction: predict t+2 from [h_t; e_{t+1}]."""
    if "mtp" not in params:
        return None
    p = params["mtp"]
    emb_next = params["embed"][tokens].astype(hidden.dtype)
    emb_next = jnp.roll(emb_next, -1, axis=1)
    fused = jnp.concatenate([hidden, emb_next], axis=-1) @ \
        p["fuse"].astype(hidden.dtype)
    positions = _positions(cfg, fused.shape[0], fused.shape[1])
    h = _dense_layer(fused, p["block"], cfg, positions)
    h = L.rms_norm(h, p["norm"])
    unembed = params.get("unembed", params["embed"])
    return jnp.einsum("bsd,vd->bsv", h, unembed.astype(h.dtype))
