"""End-to-end SZ-style compressor: Lorenzo -> quantize -> Huffman.

This is the cuSZ pipeline the paper plugs its decoders into.  The compressor
is a host-orchestrated object (codebook construction is host-side numpy, see
``core/huffman/codebook.py``); the heavy encode/decode phases are jit'd jnp
or Pallas kernels.
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.huffman import codebook as cb
from repro.core.huffman import decode as hd
from repro.core.huffman import encode as he
from repro.core.huffman import pipeline as hp
from repro.core.sz import lorenzo
from repro.runtime import trace

DEFAULT_EB = 1e-3


@dataclasses.dataclass
class Compressed:
    """A compressed tensor (host container; fields are device arrays)."""

    stream: he.EncodedStream
    codebook: cb.Codebook
    outlier_pos: jnp.ndarray   # int32[m_pad], -1 padded
    outlier_val: jnp.ndarray   # int32[m_pad] Lorenzo residuals
    shape: tuple
    dtype: np.dtype
    eb: float
    radius: int
    rel_range: float           # value range used for relative error bounds
    max_abs: float = 0.0       # max |x|, for the effective-bound guarantee

    @property
    def n_symbols(self) -> int:
        return int(np.prod(self.shape))

    @property
    def compressed_bytes(self) -> int:
        """Storage accounting (paper's compression-ratio definition)."""
        unit_bytes = int(np.ceil(int(self.stream.total_bits) / 8))
        gap_bytes = self.stream.gaps.shape[0]  # 1 B / subsequence
        n_out = int((np.asarray(self.outlier_pos) >= 0).sum())
        outlier_bytes = 8 * n_out
        codebook_bytes = 2 * (1 << self.codebook.max_len)
        return unit_bytes + gap_bytes + outlier_bytes + codebook_bytes

    @property
    def original_bytes(self) -> int:
        return int(np.prod(self.shape)) * np.dtype(self.dtype).itemsize

    @property
    def ratio(self) -> float:
        return self.original_bytes / max(self.compressed_bytes, 1)

    @property
    def quant_code_bytes(self) -> int:
        """Size of the quantization-code array (paper computes decoder GB/s
        relative to this: 2 bytes per code)."""
        return 2 * self.n_symbols

    @property
    def eb_effective(self) -> float:
        """Guaranteed bound: eb + reconstruction rounding.

        The lattice value q is exact (float64 host prequantization); the
        further rounding is the f32 product ``q * 2*eb`` at reconstruction
        (one f32 ulp at max |x|), plus -- for low-precision outputs
        (bf16/f16) -- the single final cast of that product to the output
        dtype (half an output-dtype ulp at max |x'|).
        """
        bound = self.eb + float(np.spacing(np.float32(self.max_abs + self.eb)))
        dt = np.dtype(self.dtype)
        if dt.itemsize < 4:     # bf16/f16: one final-cast rounding step
            # jnp.finfo resolves ml_dtypes (bfloat16) where np.finfo cannot.
            bound += 0.5 * float(jnp.finfo(dt).eps) * (self.max_abs + bound)
        return bound


def _outlier_m_pad(n_out: int) -> int:
    """Power-of-two side-list padding; shared by host and device gather so
    identical logical payloads get identical padded layouts."""
    return max(8, int(2 ** np.ceil(np.log2(max(n_out, 1) + 1))))


@jax.jit
def _value_stats(x):
    """``(max(x) - min(x), max |x|)`` in x's dtype, as one program."""
    return jnp.max(x) - jnp.min(x), jnp.max(jnp.abs(x))


def value_range(x) -> float:
    """``max(x) - min(x)``, the range a relative bound is taken over, read
    as :func:`compress` reads it."""
    return float(trace.to_host(_value_stats(trace.to_device(x))[0]))


@jax.jit
def _outlier_prefix(outlier):
    """Inclusive prefix count of the flattened outlier mask, and its total."""
    csum = he.prefix_sum(outlier.reshape(-1).astype(jnp.int32))
    return csum, csum[-1]


@partial(jax.jit, static_argnames=("m_pad",))
def _gather_outliers(csum, resid, m_pad: int):
    """Compact the outlier side list from an inclusive mask prefix sum.

    ``jnp.nonzero(size=...)`` lowers to a full-length scatter (serial on
    CPU, uncoalesced on accelerators); the k-th outlier's position is just
    ``searchsorted(csum, k + 1)`` -- ``m_pad`` binary searches and one
    gather, no scatter anywhere.  Ascending positions, -1/-0 padded, byte
    matching the host path's ``np.nonzero`` layout.
    """
    m = csum[-1]
    k = jnp.arange(1, m_pad + 1, dtype=jnp.int32)
    pos = jnp.searchsorted(csum, k, side="left").astype(jnp.int32)
    pos = jnp.where(k <= m, pos, -1)
    val = jnp.where(pos >= 0,
                    resid.reshape(-1)[jnp.clip(pos, 0)].astype(jnp.int32), 0)
    return pos, val


def encode_unsupported_reason(x, backend) -> "str | None":
    """Why the device encode path cannot serve this tensor (None = it can).

    The in-graph quantizer is float32 (``lorenzo.quantize``); other dtypes
    fall back to the host path -- counted in
    ``stats["encode_fallbacks"]``, never wrong.
    """
    be = hp.get_encode_backend(backend)
    if not be.device:
        return f"backend {be.name!r} is the host path"
    if jnp.asarray(x).dtype != jnp.float32:
        return f"dtype {x.dtype} is not float32 (in-graph quantizer is f32)"
    return None


def compress(
    x,
    eb: float = DEFAULT_EB,
    mode: str = "rel",
    radius: int = lorenzo.DEFAULT_RADIUS,
    max_len: int = cb.DEFAULT_MAX_LEN,
    subseqs_per_seq: int = he.DEFAULT_SUBSEQS_PER_SEQ,
    encode_backend: str = "ref",
    value_range: "float | None" = None,
) -> Compressed:
    """Compress a float tensor with error bound ``eb``.

    mode="rel": bound is ``eb * (max(x) - min(x))`` (the paper's setting,
    "relative error bound 1e-3"); mode="abs": bound is ``eb`` directly.
    ``value_range`` replaces ``max(x) - min(x)`` under "rel": a part of a
    larger tensor passes the whole tensor's range (:func:`value_range`), so
    that every part is quantized on the one lattice of the whole.

    ``encode_backend`` selects the write-path pipeline
    (``pipeline.available_encode_backends()``): "ref" is the host path
    (float64 prequantization + numpy histogram); "jnp" / "pallas" run
    quantize -> outlier gather -> histogram -> bit-pack device-resident,
    with only the ``2*radius``-entry histogram crossing to host for
    codebook construction.  Device backends quantize in float32, so for
    eb far above ulp scale (the supported regime) the codes -- and
    therefore the emitted bytes -- match the host path; inputs a device
    backend cannot serve fall back to "ref", counted in
    ``stats["encode_fallbacks"]``.
    """
    x = trace.to_device(x)
    with trace.span("compress.stats"):
        span, max_abs = (float(trace.to_host(v)) for v in _value_stats(x))
    if mode == "rel":
        rng = span if value_range is None else value_range
        rng = rng if rng > 0 else 1.0
        abs_eb = eb * rng
    elif mode == "abs":
        rng = 1.0
        abs_eb = eb
    else:
        raise ValueError(f"unknown mode {mode!r}")

    ebe = hp.get_encode_backend(encode_backend)
    if ebe.device and encode_unsupported_reason(x, ebe) is not None:
        ebe.bump("encode_fallbacks")
        ebe = hp.get_encode_backend("ref")

    if ebe.device:
        # Same int32-lattice guard the host prequantizer raises.
        if np.round(max_abs / (2.0 * abs_eb)) >= 2**31 - 1:
            raise ValueError(
                "error bound too small for int32 lattice; increase eb")
        with trace.span("compress.outliers"):
            codes, outlier, resid = ebe.quantize_fn(x, abs_eb, radius)
            codes_flat = codes.reshape(-1)
            csum, n_outliers = _outlier_prefix(outlier)
            # One scalar sync sizes the side list; the gather stays on
            # device.
            m_pad = _outlier_m_pad(int(trace.to_host(n_outliers)))
            pos_pad, val_pad = _gather_outliers(csum, resid, m_pad)
    else:
        with trace.span("compress.outliers"):
            codes_np, outlier, resid = ebe.quantize_fn(x, abs_eb, radius)
            codes_flat = codes_np.reshape(-1)

            # Outlier side list (exact residuals), padded to power-of-two
            # length.
            pos = np.nonzero(np.asarray(outlier).reshape(-1))[0].astype(
                np.int32)
            vals = np.asarray(resid).reshape(-1)[pos].astype(np.int32)
            m_pad = _outlier_m_pad(len(pos))
            pos_pad = np.full(m_pad, -1, np.int32)
            val_pad = np.zeros(m_pad, np.int32)
            pos_pad[: len(pos)] = pos
            val_pad[: len(pos)] = vals

    # Histogram -> codebook (host package-merge) -> bit-pack dispatch.
    with trace.span("compress.codebook"):
        freq = ebe.hist_fn(codes_flat, 2 * radius)
        plan = hp.build_encoder_plan(freq, max_len=max_len,
                                     subseqs_per_seq=subseqs_per_seq,
                                     backend=ebe)
    with trace.span("compress.pack"):
        stream = hp.encode_with_plan(codes_flat, plan, backend=ebe)

    return Compressed(
        stream=stream,
        codebook=plan.codebook,
        outlier_pos=trace.to_device(pos_pad),
        outlier_val=trace.to_device(val_pad),
        shape=tuple(x.shape),
        dtype=np.dtype(str(x.dtype)),
        eb=abs_eb,
        radius=radius,
        rel_range=rng,
        max_abs=max_abs,
    )


def _dequantize(c: Compressed, codes: jnp.ndarray) -> jnp.ndarray:
    return lorenzo.dequantize(
        codes.reshape(c.shape), c.outlier_pos, c.outlier_val, c.eb, c.shape,
        radius=c.radius, dtype=jnp.dtype(str(c.dtype)))


def _fused_transform(c: Compressed) -> hp.OutputTransform:
    return hp.OutputTransform(eb=c.eb, radius=c.radius,
                              outlier_pos=c.outlier_pos,
                              outlier_val=c.outlier_val,
                              shape=tuple(c.shape),
                              out_dtype=jnp.dtype(str(np.dtype(c.dtype))))


#: Output dtypes the fused epilogue serves (f32 compute, one final cast).
FUSED_DTYPES = ("float32", "bfloat16", "float16")
#: Widest fastest axis the row-tiled N-D epilogue provisions for (one tile
#: must hold at least one whole row in VMEM).
FUSED_MAX_COLS = 1 << 15


def fused_unsupported_reason(c: Compressed, backend, method: str,
                             strategy: str) -> "str | None":
    """Why the fused decode path cannot serve this tensor (None = it can).

    The fused epilogue covers N-D inverse Lorenzo (unit axes are squeezed
    first -- ``pipeline.fused_squeeze``) over float32, bfloat16 and
    float16 outputs (``FUSED_DTYPES``).  Still falling back to the
    two-pass path (recorded in ``stats["fused_fallbacks"]``): other
    dtypes, rows wider than ``FUSED_MAX_COLS``, the sequential oracle
    method, the class-gathering "tuned" strategy, and backends registered
    without fused ops.
    """
    be = hp.get_backend(backend)
    if method == "naive_ref":
        return "method 'naive_ref' is the sequential oracle"
    if strategy not in ("tile", "padded"):
        return ("strategy 'tuned' gathers sequences by CR class, which "
                "breaks the sequential reconstruction carry")
    if not be.supports_fused:
        return f"backend {be.name!r} registers no fused ops"
    if np.dtype(c.dtype).name not in FUSED_DTYPES:
        return (f"dtype {np.dtype(c.dtype)} not in fused set "
                f"{FUSED_DTYPES}")
    sq = tuple(s for s in c.shape if s != 1)
    if len(sq) >= 2 and sq[-1] > FUSED_MAX_COLS:
        return (f"fastest axis {sq[-1]} exceeds the per-tile row bound "
                f"{FUSED_MAX_COLS}")
    return None


def _guard_symbol_count(c: Compressed, plan, backend) -> None:
    """Decoder guard: a plan must decode exactly ``c.n_symbols`` symbols.

    The per-subsequence counts of a corrupt (CRC-valid-but-malformed in
    memory) stream can disagree with the tensor's recorded shape; decoding
    would then scatter a wrong number of symbols into plausible-looking
    output.  Detect it here -- where ``n_symbols == prod(shape)`` is an
    invariant -- rather than in ``pipeline.decode``, whose callers may
    legitimately decode a prefix.  Trips count in
    ``stats["decode_guard_trips"]`` and raise ``DecodeGuardError``.
    """
    if plan is None:
        return
    total = int(np.asarray(plan.seq_counts).sum())
    if total != c.n_symbols:
        hp.get_backend(backend).bump("decode_guard_trips")
        raise hp.DecodeGuardError(
            f"symbol-count mismatch: plan decodes {total} symbols but the "
            f"tensor records n_symbols={c.n_symbols} (shape "
            f"{tuple(c.shape)}) -- corrupt stream metadata")


def decompress(
    c: Compressed,
    method: str = "gap",
    tile_syms: "int | None" = None,
    *,
    backend: "str | hp.DecodeBackend" = "ref",
    strategy: str = "tile",
    t_high: int = hp.T_HIGH_DEFAULT,
    plan=None,
    fused: bool = False,
) -> jnp.ndarray:
    """Decompress; ``method`` in {"gap", "selfsync", "naive_ref"}.

    This is the raw engine function: every knob is a per-call argument.
    Application code should normally hold a configured ``repro.core.Codec``
    (which adds plan caching and a fixed policy) instead of calling this
    directly.  Decoding goes through ``core.huffman.pipeline.decode``:
    ``backend`` in ``available_backends()`` selects the jnp reference or the
    Pallas kernels (compiled on a TPU, interpreted elsewhere), ``strategy``
    in {"tuned", "tile", "padded"} selects the decode-write variant,
    ``tile_syms`` pins the "tile" strategy's tiles (``None`` sizes them
    from the plan's counts, ``pipeline.tile_geometry``), and ``plan`` may
    carry a prebuilt ``DecoderPlan``.

    ``fused=True`` requests the fused decode→dequantize→reconstruct path:
    phase 4 carries the decoded symbols straight through dequantization and
    the inverse-Lorenzo prefix sum inside the decode-write dispatch, never
    materializing the uint16 quant-code array.  Output is bit-exact with
    the two-pass path.  When the request cannot be served (see
    :func:`fused_unsupported_reason`) it silently falls back to two-pass
    decoding and increments ``backend.stats["fused_fallbacks"]``.
    """
    book = c.codebook
    n = c.n_symbols

    if plan is None and method in hp.VALID_PLAN_METHODS:
        plan = hp.build_plan(c.stream, book, method=method, backend=backend,
                             t_high=t_high)
    _guard_symbol_count(c, plan, backend)

    if fused:
        reason = fused_unsupported_reason(c, backend, method, strategy)
        if reason is None:
            out = hp.decode(c.stream, book, n, plan=plan, method=method,
                            backend=backend, strategy=strategy,
                            tile_syms=tile_syms, t_high=t_high,
                            transform=_fused_transform(c))
            return out.reshape(c.shape)
        hp.get_backend(backend).bump("fused_fallbacks")

    if method == "naive_ref":
        codes = hd.decode_sequential(jnp.asarray(c.stream.units),
                                     jnp.asarray(book.dec_sym),
                                     jnp.asarray(book.dec_len), n_symbols=n,
                                     max_len=book.max_len)
    else:
        codes = hp.decode(c.stream, book, n, plan=plan, method=method,
                          backend=backend, strategy=strategy,
                          tile_syms=tile_syms, t_high=t_high)
    return _dequantize(c, codes)


def decompress_batch(
    cs: "list[Compressed]",
    method: str = "gap",
    *,
    backend: str = "ref",
    strategy: str = "tile",
    t_high: int = hp.T_HIGH_DEFAULT,
    plans: "list | None" = None,
    fused: bool = False,
    tile_syms: "int | None" = None,
) -> list:
    """Decompress many tensors with class-batched decode dispatch.

    Huffman decode-write runs once per CR class across ALL tensors
    (``pipeline.decode_batch``) instead of once per class per tensor --
    the dispatch structure that makes restoring N checkpoint shards or
    KV-cache blocks scale with class count, not tensor count.  Output is
    bit-exact with per-tensor ``decompress``.  ``plans`` may carry prebuilt
    (e.g. cached) ``DecoderPlan`` objects, one per tensor, in which case the
    phase 1-3 rebuild is skipped entirely (the store's plan cache rides on
    this).

    ``fused=True`` trades dispatch merging for intermediate traffic:
    tensors the fused path can serve (see :func:`fused_unsupported_reason`)
    decode one-by-one through the fused kernels under ``strategy`` (zero
    quant-code HBM round trip, but one dispatch chain per tensor); the
    rest decode through the class-merged two-pass path.  ``tile_syms``
    pins the fused "tile" decodes' tiles as in :func:`decompress` (``None``
    sizes them from each plan's counts).  Eligibility is
    evaluated exactly ONCE per tensor here -- against the strategy that
    would actually run -- and every ineligible tensor bumps
    ``stats["fused_fallbacks"]`` exactly once.  Output order and bit
    patterns are unchanged either way.
    """
    cs = list(cs)
    with trace.span("decode.dispatch", n=len(cs)):
        return _decompress_batch(cs, method, backend, strategy, t_high,
                                 plans, fused, tile_syms)


def _decompress_batch(cs, method, backend, strategy, t_high, plans, fused,
                      tile_syms):
    if not cs:
        return []
    if plans is None and method in hp.VALID_PLAN_METHODS:
        plans = [hp.build_plan(c.stream, c.codebook, method=method,
                               backend=backend, t_high=t_high) for c in cs]
    if plans is not None:
        for c, p in zip(cs, plans):
            _guard_symbol_count(c, p, backend)
    if fused:
        outs: list = [None] * len(cs)
        rest = []
        be = hp.get_backend(backend)
        for i, c in enumerate(cs):
            if fused_unsupported_reason(c, be, method, strategy) is None:
                out = hp.decode(c.stream, c.codebook, c.n_symbols,
                                plan=plans[i] if plans else None,
                                method=method, backend=be,
                                strategy=strategy, tile_syms=tile_syms,
                                t_high=t_high,
                                transform=_fused_transform(c))
                outs[i] = out.reshape(c.shape)
            else:
                be.bump("fused_fallbacks")
                rest.append(i)
        if rest:
            codes = hp.decode_batch(
                [cs[i].stream for i in rest], [cs[i].codebook for i in rest],
                [cs[i].n_symbols for i in rest], method=method, backend=be,
                t_high=t_high,
                plans=[plans[i] for i in rest] if plans else None)
            for i, q in zip(rest, codes):
                outs[i] = _dequantize(cs[i], q)
        return outs
    codes = hp.decode_batch([c.stream for c in cs], [c.codebook for c in cs],
                            [c.n_symbols for c in cs], method=method,
                            backend=backend, t_high=t_high, plans=plans)
    return [_dequantize(c, q) for c, q in zip(cs, codes)]
