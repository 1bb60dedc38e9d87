"""Single-token decode paths with per-layer caches (serve_step substrate).

Cache layouts (stacked over layers, scan-carried):
  dense/vlm/moe : k,v   (L, B, S_kv, Hkv, Dh)   -- S_kv = window for SWA
  mla_moe       : latent (L, B, S_kv, kvr + dr) -- compressed latent cache
  kda_mla       : latent (n_mla, B, S, kvr + dr) + kda_state
                  (n_kda, B, H, dk, dk) fp32 + kda_conv (n_kda, B, 3 H dk,
                  K - 1) fp32
  hybrid_ssm    : ssm (L, B, H, N, P) fp32  + attn k,v (n_attn, B, S, Hkv, Dh)
  rwkv          : state (L, B, H, dk, dv) fp32 + shift carries (L, B, d) x2
  encdec        : self k,v (L, B, S, Hkv, Dh) + cross k,v precomputed
                  (L, B, T_enc, Hkv, Dh)
"""

from __future__ import annotations

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


#: The token axis of every cache tensor that has one (each family's k/v,
#: latent and int8 scales hold a sequence's tokens on axis 2).
TOKEN_AXIS = 2


def _cache_entries(cfg: ModelConfig, batch: int, kv_len: int, dtype=None):
    """``{name: (shape, dtype, token_axis)}`` of the decode cache.  A
    tensor's ``token_axis`` is None where it holds per-sequence state with
    no token axis (a recurrent state, a convolution window, the encoder's
    cross-attention k/v): a pager never slices it by token ranges."""
    dt = dtype or cfg.cdt
    fam = cfg.family
    tok = TOKEN_AXIS
    hkv, dh = cfg.n_kv_heads, cfg.head_dim
    if cfg.swa_window:
        kv_len = min(kv_len, cfg.swa_window)
    if fam in ("dense", "vlm"):
        if cfg.kv_quant:
            return {
                "k": ((cfg.n_layers, batch, kv_len, hkv, dh), jnp.int8, tok),
                "v": ((cfg.n_layers, batch, kv_len, hkv, dh), jnp.int8, tok),
                "k_scale": ((cfg.n_layers, batch, kv_len, hkv), jnp.float32,
                            tok),
                "v_scale": ((cfg.n_layers, batch, kv_len, hkv), jnp.float32,
                            tok),
            }
        return {"k": ((cfg.n_layers, batch, kv_len, hkv, dh), dt, tok),
                "v": ((cfg.n_layers, batch, kv_len, hkv, dh), dt, tok)}
    if fam == "moe":
        n = cfg.n_layers
        return {"k": ((n, batch, kv_len, hkv, dh), dt, tok),
                "v": ((n, batch, kv_len, hkv, dh), dt, tok)}
    if fam == "mla_moe":
        width = cfg.kv_lora_rank + cfg.qk_rope_dim
        return {"latent": ((cfg.n_layers, batch, kv_len, width), dt, tok)}
    if fam == "kda_mla":
        width = cfg.kv_lora_rank + cfg.qk_rope_dim
        h, dk = KDA.kda_dims(cfg)
        n_kda = len(cfg.kda_layers)
        return {
            "latent": ((len(cfg.mla_layers), batch, kv_len, width), dt, tok),
            "kda_state": ((n_kda, batch, h, dk, dk), jnp.float32, None),
            "kda_conv": ((n_kda, batch, 3 * h * dk, cfg.kda_conv - 1),
                         jnp.float32, None),
        }
    if fam == "hybrid_ssm":
        dv, h, p = SSM.ssm_dims(cfg)
        n_attn = max(1, cfg.n_layers // max(cfg.hybrid_attn_every, 1))
        return {
            "ssm": ((cfg.n_layers, batch, h, cfg.ssm_state, p), jnp.float32,
                    None),
            "k": ((n_attn, batch, kv_len, hkv, dh), dt, tok),
            "v": ((n_attn, batch, kv_len, hkv, dh), dt, tok),
        }
    if fam == "rwkv":
        h, dh_r = RWKV.rwkv_dims(cfg)
        return {
            "state": ((cfg.n_layers, batch, h, dh_r, dh_r), jnp.float32,
                      None),
            "tshift": ((cfg.n_layers, batch, cfg.d_model), dt, None),
            "cshift": ((cfg.n_layers, batch, cfg.d_model), dt, None),
        }
    if fam == "encdec":
        n = cfg.n_layers
        return {
            "k": ((n, batch, kv_len, hkv, dh), dt, tok),
            "v": ((n, batch, kv_len, hkv, dh), dt, tok),
            "xk": ((n, batch, cfg.encoder_seq, hkv, dh), dt, None),
            "xv": ((n, batch, cfg.encoder_seq, hkv, dh), dt, None),
        }
    raise ValueError(fam)


def cache_spec(cfg: ModelConfig, batch: int, kv_len: int, dtype=None):
    """Shape/dtype tree of the decode cache (also used by input_specs())."""
    return {k: (shape, dt) for k, (shape, dt, _) in
            _cache_entries(cfg, batch, kv_len, dtype).items()}


def cache_layout(cfg: ModelConfig) -> dict:
    """``{name: token_axis}`` of the decode cache (``None``: no token axis,
    the tensor is paged out whole, as a snapshot).  ``store.KVPager`` takes
    it as ``layout=``."""
    return {k: ax for k, (_, _, ax) in _cache_entries(cfg, 1, 1).items()}


def init_cache(cfg: ModelConfig, batch: int, kv_len: int):
    return {k: jnp.zeros(shape, dt)
            for k, (shape, dt) in cache_spec(cfg, batch, kv_len).items()}


# ---------------------------------------------------------------------------
# Per-family decode
# ---------------------------------------------------------------------------


def _dense_decode_layer(x, lp, cfg, ck, cv, pos, window=None, enc_feats=None):
    h, ck, cv = A.decode_attn(L.rms_norm(x, lp["ln1"]), lp["attn"], cfg,
                              ck, cv, pos, window=window)
    x = x + h
    if enc_feats is not None:
        xk, xv = enc_feats
        hq, hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
        xn = L.rms_norm(x, lp["ln_x"])
        q = jnp.einsum("bsd,dhe->bshe", xn, lp["xattn"]["wq"].astype(x.dtype))
        b = x.shape[0]
        g = hq // hkv
        s = jnp.einsum("bqhgd,bkhd->bhgqk",
                       q.reshape(b, 1, hkv, g, dh).astype(jnp.float32),
                       xk.astype(jnp.float32)) * dh ** -0.5
        a_ = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bhgqk,bkhd->bqhgd", a_,
                       xv.astype(jnp.float32)).reshape(b, 1, hq, dh)
        x = x + jnp.einsum("bshe,hed->bsd", o.astype(x.dtype),
                           lp["xattn"]["wo"].astype(x.dtype))
    x = x + L.mlp_apply(L.rms_norm(x, lp["ln2"]), lp["mlp"], cfg.act)
    return x, ck, cv


def forward_decode(params, token, cache, pos, cfg: ModelConfig):
    """token: (B, 1) int32; pos: int32 scalar (current absolute position).

    Returns (logits (B, 1, V), new_cache).
    """
    x = params["embed"][token].astype(cfg.cdt)
    fam = cfg.family
    new_cache = dict(cache)

    if fam in ("dense", "vlm") and cfg.kv_quant:
        def body(x, inp):
            lp, kc = inp
            h, kc = A.decode_attn_int8(L.rms_norm(x, lp["ln1"]), lp["attn"],
                                       cfg, kc, pos, window=cfg.swa_window)
            x = x + h
            x = x + L.mlp_apply(L.rms_norm(x, lp["ln2"]), lp["mlp"], cfg.act)
            return x, kc

        kcache = {k: cache[k] for k in ("k", "v", "k_scale", "v_scale")}
        x, kc = jax.lax.scan(body, x, (params["layers"], kcache))
        new_cache.update(kc)

    elif fam in ("dense", "vlm", "moe"):
        def body(x, inp):
            lp, ck, cv = inp
            if fam == "moe":
                h, ck, cv = A.decode_attn(L.rms_norm(x, lp["ln1"]),
                                          lp["attn"], cfg, ck, cv, pos,
                                          window=cfg.swa_window)
                x = x + h
                mo, _ = MOE.moe_block(L.rms_norm(x, lp["ln2"]), lp["moe"], cfg)
                x = x + mo
            else:
                x, ck, cv = _dense_decode_layer(x, lp, cfg, ck, cv, pos,
                                                window=cfg.swa_window)
            return x, (ck, cv)

        layers = params["layers"]
        if fam == "moe" and "dense_layers" in params:
            raise NotImplementedError  # qwen2-moe has no dense prefix
        x, kv = jax.lax.scan(body, x, (layers, cache["k"], cache["v"]))
        new_cache["k"], new_cache["v"] = kv

    elif fam == "mla_moe":
        nk = cfg.first_k_dense
        lat = cache["latent"]

        def dbody(x, inp):
            lp, lat_l = inp
            h, lat_l = MLA.mla_decode(L.rms_norm(x, lp["ln1"]), lp["attn"],
                                      cfg, lat_l, pos)
            x = x + h
            x = x + L.mlp_apply(L.rms_norm(x, lp["ln2"]), lp["mlp"], cfg.act)
            return x, lat_l

        def mbody(x, inp):
            lp, lat_l = inp
            h, lat_l = MLA.mla_decode(L.rms_norm(x, lp["ln1"]), lp["attn"],
                                      cfg, lat_l, pos)
            x = x + h
            mo, _ = MOE.moe_block(L.rms_norm(x, lp["ln2"]), lp["moe"], cfg)
            return x + mo, lat_l

        lat_dense, lat_moe = lat[:nk], lat[nk:]
        if nk:
            x, lat_dense = jax.lax.scan(dbody, x,
                                        (params["dense_layers"], lat_dense))
        x, lat_moe = jax.lax.scan(mbody, x, (params["layers"], lat_moe))
        new_cache["latent"] = jnp.concatenate([lat_dense, lat_moe], axis=0) \
            if nk else lat_moe

    elif fam == "kda_mla":
        lat, st, win = cache["latent"], cache["kda_state"], cache["kda_conv"]
        for i, (kind, j) in enumerate(KDA.layer_order(cfg)):
            lp = jax.tree.map(lambda a: a[j], params[kind])
            xn = L.rms_norm(x, lp["ln1"])
            if kind == "kda":
                y, s_j, w_j = KDA.kda_block(xn, lp["kda"], cfg, st[j], win[j])
                st, win = st.at[j].set(s_j), win.at[j].set(
                    w_j.astype(win.dtype))
            else:
                y, l_j = MLA.mla_decode(xn, lp["attn"], cfg, lat[j], pos)
                lat = lat.at[j].set(l_j)
            x, _ = KDA.ffn(x + y, params, cfg, i)
        new_cache.update(latent=lat, kda_state=st, kda_conv=win)

    elif fam == "hybrid_ssm":
        every = max(cfg.hybrid_attn_every, 1)
        shared = params["shared_attn"]
        n_attn = max(1, cfg.n_layers // every)

        # scan over ssm layers; attention caches are indexed by invocation.
        def body(carry, inp):
            x, idx, ck_all, cv_all = carry
            lp, sstate = inp
            xn = L.rms_norm(x, lp["ln1"])
            h, sstate = SSM.ssm_decode(xn, lp["ssm"], cfg, sstate)
            x = x + h

            def with_attn(args):
                x, ck_all, cv_all = args
                inv = jnp.minimum(idx // every, n_attn - 1)
                ck = ck_all[inv]
                cv = cv_all[inv]
                h, ck, cv = A.decode_attn(L.rms_norm(x, shared["ln1"]),
                                          shared["attn"], cfg, ck, cv, pos)
                x = x + h
                x = x + L.mlp_apply(L.rms_norm(x, shared["ln2"]),
                                    shared["mlp"], cfg.act)
                ck_all = jax.lax.dynamic_update_index_in_dim(ck_all, ck, inv, 0)
                cv_all = jax.lax.dynamic_update_index_in_dim(cv_all, cv, inv, 0)
                return x, ck_all, cv_all

            use_attn = (idx % every) == (every - 1)
            x, ck_all, cv_all = jax.lax.cond(
                use_attn, with_attn, lambda a: a, (x, ck_all, cv_all))
            return (x, idx + 1, ck_all, cv_all), sstate

        (x, _, ck_all, cv_all), sstates = jax.lax.scan(
            body, (x, jnp.int32(0), cache["k"], cache["v"]),
            (params["layers"], cache["ssm"]))
        new_cache["ssm"] = sstates
        new_cache["k"], new_cache["v"] = ck_all, cv_all

    elif fam == "rwkv":
        def body(x, inp):
            lp, st, ts, cs = inp
            y, ts, st = RWKV.time_mix(L.rms_norm(x, lp["ln1"]), ts, st,
                                      lp["tmix"], cfg)
            x = x + y
            y, cs = RWKV.channel_mix(L.rms_norm(x, lp["ln2"]), cs,
                                     lp["cmix"], cfg)
            return x + y, (st, ts, cs)

        x, (st, ts, cs) = jax.lax.scan(
            body, x, (params["layers"], cache["state"], cache["tshift"],
                      cache["cshift"]))
        new_cache["state"], new_cache["tshift"], new_cache["cshift"] = \
            st, ts, cs

    elif fam == "encdec":
        def body(x, inp):
            lp, ck, cv, xk, xv = inp
            x, ck, cv = _dense_decode_layer(x, lp, cfg, ck, cv, pos,
                                            enc_feats=(xk, xv))
            return x, (ck, cv)

        x, kv = jax.lax.scan(body, x, (params["layers"], cache["k"],
                                       cache["v"], cache["xk"], cache["xv"]))
        new_cache["k"], new_cache["v"] = kv
    else:
        raise ValueError(fam)

    x = L.rms_norm(x, params["final_norm"])
    unembed = params.get("unembed", params["embed"])
    logits = jnp.einsum("bsd,vd->bsv", x, unembed.astype(x.dtype))
    return logits, new_cache
