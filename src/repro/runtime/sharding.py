"""Parameter / activation sharding rules (GSPMD, ZeRO-3 + TP + EP).

Rules are keyed on (path substring, trailing-ndim) of each parameter leaf;
stacked-layer leading dims are never sharded.  Every axis assignment is
divisibility-checked against the mesh: a dim too small for its axis falls
back to replication (e.g. kv_heads=2 on a 16-way model axis), a dim >= the
axis size but not divisible is left to GSPMD padding (e.g. 60 experts).

Scheme (DESIGN.md §6):
  * "model": heads / d_ff / experts / vocab  (TP + EP + vocab-parallel)
  * ("pod","data"): the other large dim of every matrix  (ZeRO-3 / FSDP --
    XLA inserts per-layer all-gathers under the layer scan and overlaps them
    with compute)
  * activations: batch on ("pod","data")
"""

from __future__ import annotations

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.launch.mesh import batch_axes, model_axis


def _axis_size(mesh, axes) -> int:
    if axes is None:
        return 1
    if isinstance(axes, str):
        axes = (axes,)
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return n


def _fit(mesh, dim_size: int, axes):
    """Return ``axes`` if the dim divides evenly over them, else None.

    jit argument shardings require exact divisibility (GSPMD padding is
    only available to *internal* values), so anything that does not divide
    falls back to replication and the rule set must route the sharding to a
    dim that does (e.g. expert-TP instead of EP for 60 experts)."""
    n = _axis_size(mesh, axes)
    if n <= 1:
        return None
    if dim_size % n == 0:
        return axes
    return None


def param_spec(path: str, shape: tuple, mesh) -> P:
    """PartitionSpec for one parameter leaf."""
    fsdp = batch_axes(mesh)
    tp = model_axis(mesh)
    nd = len(shape)

    def spec(*trailing):
        """Pad with None for stacked leading dims, divisibility-check."""
        lead = nd - len(trailing)
        checked = tuple(_fit(mesh, shape[lead + i], ax)
                        for i, ax in enumerate(trailing))
        return P(*((None,) * lead + checked))

    # --- order matters: more specific substrings first ---
    if "moe/router" in path or path.endswith("router"):
        return spec(fsdp, None)                       # (d, E)
    if "moe/shared" in path:
        if path.endswith("wd"):
            return spec(tp, fsdp)                     # (ds, d)
        return spec(fsdp, tp)                         # (d, ds)
    if "moe/" in path:
        # Expert-TP (default): every expert's FFN is sharded over "model"
        # (d_expert) and "data" (d_model).  Unlike expert-parallel (E over
        # "model"), this needs no dispatch all-to-all and no divisibility
        # of E (60 experts on a 16-way axis).
        # REPRO_MOE_SHARDING=ep switches to expert-parallel (E on "model",
        # d_model on fsdp) -- the §Perf B hillclimb comparison.
        import os
        if os.environ.get("REPRO_MOE_SHARDING", "tp") == "ep":
            if path.endswith("wd"):
                return spec(tp, None, fsdp)           # (E, de, d)
            return spec(tp, fsdp, None)               # (E, d, de)
        if path.endswith("wd"):
            return spec(None, tp, fsdp)               # (E, de, d)
        return spec(None, fsdp, tp)                   # (E, d, de)

    # --- MLA ---
    if path.endswith(("wdq", "wdkv")):
        return spec(fsdp, None)                       # (d, r)
    if path.endswith(("wuq", "wuk", "wuv")):
        return spec(None, tp, None)                   # (r, h, e)

    # --- attention ---
    if path.endswith(("attn/wq", "attn/wk", "attn/wv", "xattn/wq",
                      "xattn/wk", "xattn/wv")):
        return spec(fsdp, tp, None)                   # (d, h, dh)
    if path.endswith(("attn/wo", "xattn/wo")):
        return spec(tp, None, fsdp)                   # (h, dh, d)
    if path.endswith(("bq", "bk", "bv")):
        return spec(tp, None)                         # (h, dh)

    # --- MLP ---
    if path.endswith(("mlp/wg", "mlp/wu", "mlp/wi", "cmix/wk")):
        return spec(fsdp, tp)                         # (d, ff)
    if path.endswith(("mlp/wd", "mlp/wo", "cmix/wv")):
        return spec(tp, fsdp)                         # (ff, d)

    # --- KDA (kimi-linear) ---
    if path.endswith(("kda/wq", "kda/wk", "kda/wv", "kda/wfb", "kda/wgb")):
        return spec(fsdp, tp)                         # (d or dk, H dk)
    if path.endswith("kda/wo"):
        return spec(tp, fsdp)                         # (H dk, d)

    # --- SSM / RWKV ---
    if path.endswith("ssm/win"):
        return spec(fsdp, tp)
    if path.endswith("ssm/wout"):
        return spec(tp, fsdp)
    if path.endswith(("tmix/wr", "tmix/wk", "tmix/wv", "tmix/wg", "cmix/wr")):
        return spec(fsdp, tp)                         # (d, d)
    if path.endswith("tmix/wo"):
        return spec(tp, fsdp)
    if path.endswith("w_lora_a"):
        return spec(fsdp, None)
    if path.endswith("w_lora_b"):
        return spec(None, fsdp)

    # --- embeddings / heads ---
    if path.endswith(("embed", "unembed")):
        # vocab-parallel only: FSDP on d here puts the "data" axis on the
        # contraction dim of the CE dots whose batch dim is also "data",
        # which pushes GSPMD into full-vocab all-gathers in the CE backward
        # (9.3 GiB/chip at 152k vocab).  Vocab/16 already makes the table
        # small (<200 MB/chip for every assigned arch).
        return spec(tp, None)                         # (V, d)
    if path.endswith("mtp/fuse"):
        return spec(fsdp, None)

    # --- everything else (norms, biases, scalars): replicate ---
    return P()


def _path_str(key_path) -> str:
    parts = []
    for k in key_path:
        if hasattr(k, "key"):
            parts.append(str(k.key))
        elif hasattr(k, "idx"):
            parts.append(str(k.idx))
        else:
            parts.append(str(k))
    return "/".join(parts)


def param_shardings(params_shape, mesh):
    """NamedSharding tree matching a params (shape-)tree."""

    def leaf(kp, x):
        return NamedSharding(mesh, param_spec(_path_str(kp), x.shape, mesh))

    return jax.tree_util.tree_map_with_path(leaf, params_shape)


def opt_state_shardings(opt_shape, mesh, params_shape=None):
    """Optimizer-state sharding.

    fp32 m/v mirror the param spec.  int8 block-quantized leaves ("q",
    "scale") are flat (blocks, 128)/(blocks, 1): shard the block dim over
    *all* mesh axes when divisible (fully-sharded optimizer state, the
    deepseek-v3 fit requirement), else replicate.
    """
    all_axes = tuple(mesh.axis_names)

    def leaf(kp, x):
        path = _path_str(kp)
        if path.endswith("step"):
            return NamedSharding(mesh, P())
        # strip the leading "m/"/"v/" and any quantized-leaf suffix so the
        # state leaf reuses its param's rules (q/scale keep the param shape,
        # so the same spec applies; scale's smaller last dim is re-checked
        # for divisibility by param_spec itself).
        sub = path.split("/", 1)[1] if "/" in path else path
        for suffix in ("/q", "/scale", "/f"):
            if sub.endswith(suffix):
                sub = sub[: -len(suffix)]
                break
        return NamedSharding(mesh, param_spec(sub, x.shape, mesh))

    return jax.tree_util.tree_map_with_path(leaf, opt_shape)


def batch_shardings(batch_shape, mesh):
    """Input batch: shard the leading (batch) dim over ("pod","data")."""
    fsdp = batch_axes(mesh)

    def leaf(x):
        if x.ndim == 0:
            return NamedSharding(mesh, P())
        ax = _fit(mesh, x.shape[0], fsdp)
        return NamedSharding(mesh, P(*((ax,) + (None,) * (x.ndim - 1))))

    return jax.tree_util.tree_map(leaf, batch_shape)


def cache_shardings(cache_shape, mesh, cfg=None):
    """Decode caches: (L, B, S, H, Dh) -- batch on fsdp, heads on model.

    For batch=1 (long_500k) the batch axis falls back to replication via the
    divisibility check; heads still shard on "model".
    """
    fsdp = batch_axes(mesh)
    tp = model_axis(mesh)

    def leaf(kp, x):
        name = _path_str(kp).rsplit("/", 1)[-1]
        b_ax = _fit(mesh, x.shape[1], fsdp) if x.ndim >= 2 else None
        if name in ("k_scale", "v_scale"):      # (L, B, S, Hkv)
            h_ax = _fit(mesh, x.shape[3], tp)
            return NamedSharding(mesh, P(None, b_ax, None, h_ax))
        if name in ("k", "v", "xk", "xv"):      # (L, B, S, Hkv, D)
            h_ax = _fit(mesh, x.shape[3], tp)
            if h_ax is not None:
                return NamedSharding(mesh, P(None, b_ax, None, h_ax, None))
            # kv heads too few for the model axis: sequence-shard the cache
            # (flash-decoding style; softmax becomes distributed max/sum)
            s_ax = _fit(mesh, x.shape[2], tp)
            return NamedSharding(mesh, P(None, b_ax, s_ax, None, None))
        if name in ("ssm", "state"):            # (L, B, H, N/dk, P/dv)
            h_ax = _fit(mesh, x.shape[2], tp)
            return NamedSharding(mesh, P(None, b_ax, h_ax, None, None))
        if name == "latent":                    # (L, B, S, kvr+dr)
            w_ax = _fit(mesh, x.shape[3], tp)
            return NamedSharding(mesh, P(None, b_ax, None, w_ax))
        if x.ndim == 3:                         # (L, B, d) shift carries
            return NamedSharding(mesh, P(None, b_ax, None))
        return NamedSharding(mesh, P())

    return jax.tree_util.tree_map_with_path(leaf, cache_shape)
