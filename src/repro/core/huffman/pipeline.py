"""Unified plan/execute decoder pipeline (single entry point for decoding).

The paper's decode stack is a fixed phase sequence -- sync-point discovery
(gap array or self-synchronization), per-subsequence count, output-offset
prefix sum, then the tuned tile-staged decode-write.  This module factors
that sequence into two layers so every consumer (``core/sz/compressor``,
``checkpoint/manager``, ``models/kvcache``, the benchmarks) calls one API:

    build_plan()    phases 1-3 + the online tuner's per-CR-class dispatch
                    plan (paper Alg. 2): sync starts, counts, output
                    offsets, CR classes, per-class tile sizes.
    decode()        phase 4 through a named *backend*; strategies:
                    "tuned"  per-CR-class tile decode (paper Alg. 1 + 2),
                    "tile"   fixed-tile staged decode (paper Alg. 1),
                    "padded" padded-layout baseline (the original decoders'
                             uncoalesced-write cost structure).
    decode_batch()  class-merged decode of MANY tensors: sequences of equal
                    CR class from all tensors are gathered into one
                    decode-write dispatch, so N checkpoint shards or
                    KV-cache blocks cost one dispatch per class instead of
                    N x classes (the cuSZ+-style batched dispatch).

Backends live in a small registry: "ref" is the pure-jnp reference
(``core.huffman.decode``), "pallas" the kernel path (``repro.kernels.ops``,
imported lazily so core stays jnp-only until kernels are requested).  Every
backend counts its decode-write dispatches in ``backend.stats`` -- tests
assert the batched path issues at most one dispatch per CR class.
"""

from __future__ import annotations

import dataclasses
import functools
import threading
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.huffman import codebook as _cb
from repro.core.huffman import decode as hd
from repro.core.huffman.bits import SUBSEQ_BITS, UNIT_BITS
from repro.core.huffman.encode import EncodedStream


class DecodeGuardError(RuntimeError):
    """A decoder-level integrity guard tripped on malformed input.

    Raised by ``build_plan`` (corrupt codebook: Kraft violation, lengths
    over ``max_len``, bad LUT shapes) and by the symbol-count guard in
    ``sz.compressor.decompress`` when a CRC-valid-but-malformed stream
    would decode the wrong number of symbols.  Every trip -- including
    non-raising containment such as gap clamping -- is counted in
    ``backend.stats["decode_guard_trips"]``.
    """

# Paper Alg. 2 constants: class c in {1..T_high} covers CR in (c-1, c];
# class T_high+1 covers (T_high, 16].
T_HIGH_DEFAULT = 8          # paper's V100 value; VMEM budget gives the same
OVERFLOW_TILE = 3584        # paper: optimal buffer for CR > T_high on V100
SYMBOL_BYTES = 2
DEFAULT_TILE_SYMS = 4096

#: Decode-write strategies accepted by ``decode`` (and ``CodecConfig``).
VALID_STRATEGIES = ("tuned", "tile", "padded")
#: Sync-discovery methods accepted by ``build_plan`` / ``decode_batch``.
VALID_PLAN_METHODS = ("gap", "selfsync")


def ss_max_for_tile(tile_syms: int, max_len: int) -> int:
    """Static bound on subsequences overlapping one ``tile_syms`` output tile.

    Every codeword is at most ``max_len`` bits, so a 128-bit subsequence
    contains at least ``(SUBSEQ_BITS - max_len) // max_len + 1`` codeword
    starts (``Codebook.min_starts_per_subseq``).  A tile therefore overlaps
    at most ``tile_syms / min_starts`` whole subsequences, plus one partial
    subsequence at each edge.  This is the single audited home of the
    formula -- the decode-write kernels' lane provisioning and the VMEM
    scratch sizing both key off it.
    """
    min_starts = (SUBSEQ_BITS - max_len) // max_len + 1
    return tile_syms // min_starts + 2


# ---------------------------------------------------------------------------
# CR classification (paper Alg. 2: CLASSIFY / HISTOGRAM / SORT / plan)
# ---------------------------------------------------------------------------


def sequence_ratios(seq_counts: jnp.ndarray, subseqs_per_seq: int):
    """Per-sequence compression ratio: decoded bytes / encoded bytes."""
    enc_bytes = subseqs_per_seq * SUBSEQ_BITS // 8
    return seq_counts.astype(jnp.float32) * SYMBOL_BYTES / enc_bytes


def classify(ratios: jnp.ndarray, t_high: int = T_HIGH_DEFAULT):
    """CLASSIFYCR: CR in (c-1, c] -> class c; CR > t_high -> t_high + 1."""
    cls = jnp.ceil(ratios).astype(jnp.int32)
    return jnp.clip(cls, 1, t_high + 1)


def class_histogram(classes: jnp.ndarray, t_high: int = T_HIGH_DEFAULT):
    """ParHISTOGRAM (jnp fallback; the Pallas kernel lives in repro.kernels)."""
    return jnp.bincount(classes, length=t_high + 2)


def sort_by_class(classes: jnp.ndarray):
    """ParKeyValueSort: stable key-value sort of sequence ids by class."""
    idx = jnp.arange(classes.shape[0], dtype=jnp.int32)
    keys, vals = jax.lax.sort_key_val(classes, idx, is_stable=True)
    return keys, vals


@functools.partial(jax.jit, static_argnames=("subseqs_per_seq", "t_high"))
def _classify_sequences(seq_counts, subseqs_per_seq: int, t_high: int):
    """CLASSIFY, HISTOGRAM and SORT of Alg. 2 in one program: returns
    ``(classes, class histogram, sequence ids sorted by class)``."""
    classes = classify(sequence_ratios(seq_counts, subseqs_per_seq), t_high)
    return (classes, class_histogram(classes, t_high),
            sort_by_class(classes)[1])


def tile_for_class(c: int, t_high: int = T_HIGH_DEFAULT) -> int:
    """Buffer (tile) size for a class: 1024 symbols per CR unit, as in the
    paper ("sequences in the (3,4] group ... buffer of length 4096"), with
    the overflow class pinned at OVERFLOW_TILE."""
    if c > t_high:
        return OVERFLOW_TILE
    return 1024 * max(c, 1)


@dataclasses.dataclass
class ClassPlan:
    """Host-side per-CR-class dispatch plan (per-class sequence id lists)."""

    t_high: int
    classes: np.ndarray          # int32[n_seq]
    seq_order: np.ndarray        # int32[n_seq] sequence ids sorted by class
    class_start: np.ndarray      # int32[t_high+3] prefix offsets into seq_order
    tile_syms: dict              # class -> tile size

    def class_seq_ids(self, c: int) -> np.ndarray:
        lo, hi = int(self.class_start[c]), int(self.class_start[c + 1])
        return self.seq_order[lo:hi]


def make_plan(stream, seq_counts, subseqs_per_seq: int,
              t_high: int = T_HIGH_DEFAULT) -> ClassPlan:
    """Build the per-CR-class dispatch plan from per-sequence symbol counts.

    ``stream`` is accepted (and ignored) so callers that already hold the
    encoded stream can pass it alongside its metadata unchanged.
    """
    del stream
    classes, hist, order = _classify_sequences(
        jnp.asarray(seq_counts), subseqs_per_seq, t_high)
    class_start = np.zeros(t_high + 3, np.int32)
    class_start[1:] = np.cumsum(np.asarray(hist))
    return ClassPlan(
        t_high=t_high,
        classes=np.asarray(classes),
        seq_order=np.asarray(order),
        class_start=class_start,
        tile_syms={c: tile_for_class(c, t_high) for c in range(1, t_high + 2)},
    )


# ---------------------------------------------------------------------------
# Backend registry
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class OutputTransform:
    """Fused decode epilogue: dequantization + inverse Lorenzo, attached to
    a decode call so phase 4 emits reconstructed floats directly.

    The transform is ``x = 2*eb * cumsum(code - radius)`` with the outlier
    side list (``outlier_pos`` int32[m_pad] flat positions, -1 padded;
    ``outlier_val`` the exact residuals) scattered in before the prefix sum
    -- exactly ``core.sz.lorenzo.dequantize``.  Backends that register
    fused phase-4 ops apply it inside the decode-write dispatch, so the
    uint16 quant-code array is never materialized in HBM between decode and
    reconstruction.

    ``shape`` selects the reconstruction geometry: ``None`` (or any shape
    with at most one non-unit axis) runs the 1-D chained-carry epilogue,
    N-D shapes run the row-carry epilogue with one carry per outer axis
    (cumsum along every axis).  ``out_dtype`` is the reconstruction output
    dtype; the epilogue computes in f32 and casts once at the end, matching
    ``lorenzo.dequantize`` bit-for-bit for bf16/f16.  Both default to the
    historical 1-D float32 behavior.
    """

    eb: float
    radius: int
    outlier_pos: Any
    outlier_val: Any
    shape: Any = None
    out_dtype: Any = None


@dataclasses.dataclass
class DecodeBackend:
    """One implementation of the decode phases.

    ``count_fn``  (units, ds, dl, start_abs, end_abs, total_bits, max_len)
                  -> counts
    ``sync_fn``   (units, ds, dl, total_bits, n_subseq, sps, max_len,
                  early_exit) -> (start_abs, counts)
    ``tiles_fn``  phase-4 tile decode; signature of
                  ``decode.decode_write_tiles`` (+ optional ``lut_base``)
    ``padded_fn`` phase-4 padded baseline: (units, ds, dl, start_abs,
                  end_abs, total_bits, max_len, n_out) -> out

    Optional fused phase-4 ops (decode + dequantize + reconstruct in one
    dispatch; see :class:`OutputTransform`):

    ``fused_tiles_fn``   tiles_fn signature + (opos, oval, eb, radius,
                         shape=, out_dtype=) -> reconstructed
                         ``out_dtype[n_out]`` (flat, C-order)
    ``fused_padded_fn``  padded_fn signature + (opos, oval, eb, radius,
                         shape=, out_dtype=) -> reconstructed
                         ``out_dtype[n_out]`` (flat, C-order)

    A backend registered without them still works everywhere; fused
    requests fall back to the two-pass path and the fallback is recorded
    in ``stats["fused_fallbacks"]``.
    """

    name: str
    count_fn: Callable
    sync_fn: Callable
    tiles_fn: Callable
    padded_fn: Callable
    fused_tiles_fn: "Callable | None" = None
    fused_padded_fn: "Callable | None" = None
    stats: dict = dataclasses.field(
        default_factory=lambda: {"decode_write_dispatches": 0,
                                 "plan_builds": 0,
                                 "fused_dispatches": 0,
                                 "fused_fallbacks": 0,
                                 "decode_guard_trips": 0})
    _stats_lock: threading.Lock = dataclasses.field(
        default_factory=threading.Lock, repr=False, compare=False)

    @property
    def supports_fused(self) -> bool:
        return (self.fused_tiles_fn is not None
                and self.fused_padded_fn is not None)

    def bump(self, key: str, n: int = 1):
        """Atomic counter increment: one backend handle is shared by every
        codec on that backend, including N serving threads decoding through
        one scheduler, so a bare ``+=`` would drop counts."""
        with self._stats_lock:
            self.stats[key] += n

    def reset_stats(self):
        with self._stats_lock:
            for k in self.stats:
                self.stats[k] = 0

    # Counted dispatch wrappers: every phase-4 launch goes through these.
    def decode_tiles(self, *args, **kwargs):
        self.bump("decode_write_dispatches")
        return self.tiles_fn(*args, **kwargs)

    def decode_padded(self, *args, **kwargs):
        self.bump("decode_write_dispatches")
        return self.padded_fn(*args, **kwargs)

    def decode_tiles_fused(self, *args, **kwargs):
        self.bump("decode_write_dispatches")
        self.bump("fused_dispatches")
        return self.fused_tiles_fn(*args, **kwargs)

    def decode_padded_fused(self, *args, **kwargs):
        self.bump("decode_write_dispatches")
        self.bump("fused_dispatches")
        return self.fused_padded_fn(*args, **kwargs)


_BACKEND_FACTORIES: dict[str, Callable[[], DecodeBackend]] = {}
_BACKENDS: dict[str, DecodeBackend] = {}


def register_backend(name: str, factory: Callable[[], DecodeBackend]):
    """Register (or replace) a decode backend under ``name``.

    ``factory`` is a zero-argument callable returning a ``DecodeBackend``;
    it runs lazily on the first ``get_backend(name)`` so expensive imports
    (e.g. the Pallas kernels) are deferred until the backend is requested.
    Re-registering a name drops the previously constructed handle, so the
    next ``get_backend`` call sees the new factory.  Backends may omit the
    fused phase-4 ops (``fused_tiles_fn`` / ``fused_padded_fn``); fused
    requests then fall back to two-pass decoding, counted in
    ``stats["fused_fallbacks"]``.
    """
    _BACKEND_FACTORIES[name] = factory
    _BACKENDS.pop(name, None)


def available_backends() -> list[str]:
    return sorted(_BACKEND_FACTORIES)


def get_backend(backend: "str | DecodeBackend") -> DecodeBackend:
    if isinstance(backend, DecodeBackend):
        return backend
    if backend not in _BACKEND_FACTORIES:
        raise ValueError(
            f"unknown backend {backend!r}; available: {available_backends()}")
    if backend not in _BACKENDS:
        _BACKENDS[backend] = _BACKEND_FACTORIES[backend]()
    return _BACKENDS[backend]


def _make_ref_backend() -> DecodeBackend:
    def count(units, ds, dl, start_abs, end_abs, total_bits, max_len):
        _, counts = hd.subseq_scan(jnp.asarray(units), ds, dl, start_abs,
                                   end_abs, total_bits, max_len)
        return counts

    def sync(units, ds, dl, total_bits, n_subseq, sps, max_len,
             early_exit=True):
        units = jnp.asarray(units)
        start, _ = hd.selfsync_intra(units, ds, dl, total_bits, n_subseq,
                                     max_len, sps, early_exit=early_exit)
        start, _ = hd.selfsync_inter(units, ds, dl, start, total_bits,
                                     max_len, sps)
        ends = jnp.arange(n_subseq, dtype=jnp.int32) * SUBSEQ_BITS + SUBSEQ_BITS
        _, counts = hd.subseq_scan(units, ds, dl, start, ends, total_bits,
                                   max_len)
        return start, counts

    def padded(units, ds, dl, start_abs, end_abs, total_bits, max_len, n_out):
        del end_abs  # the padded reference derives windows from boundaries
        out, _ = hd.decode_write(jnp.asarray(units), ds, dl, start_abs,
                                 total_bits, max_len, n_out)
        return out

    def _epilogue(codes, n_out, opos, oval, eb, radius, shape, out_dtype):
        # Lazy import: core.sz -> compressor -> pipeline at package import
        # time, so pipeline cannot import core.sz at its own top level.
        from repro.core.sz import lorenzo

        shape = tuple(shape) if shape is not None else (n_out,)
        dtype = jnp.dtype(out_dtype) if out_dtype is not None else jnp.float32
        out = lorenzo.dequantize(codes.reshape(shape),
                                 jnp.asarray(opos, jnp.int32),
                                 jnp.asarray(oval, jnp.int32), eb, shape,
                                 radius=radius, dtype=dtype)
        return out.reshape(-1)

    # The ref backend composes the existing jnp paths (decode, then the
    # exact N-D dequantize/reconstruct the two-pass path uses), so fused-
    # vs-two-pass parity is testable on every platform by construction:
    # these are the jnp mirrors of ``kernels/fused_decode.py`` for every
    # supported ndim/dtype.
    def fused_tiles(units, ds, dl, starts, ends, offsets, total_bits,
                    max_len, n_out, tile_syms, ss_max, opos, oval, eb,
                    radius, shape=None, out_dtype=None, **kwargs):
        codes = hd.decode_write_tiles(jnp.asarray(units), ds, dl, starts,
                                      ends, offsets, total_bits, max_len,
                                      n_out, tile_syms, ss_max, **kwargs)
        return _epilogue(codes, n_out, opos, oval, eb, radius, shape,
                         out_dtype)

    def fused_padded(units, ds, dl, start_abs, end_abs, total_bits, max_len,
                     n_out, opos, oval, eb, radius, shape=None,
                     out_dtype=None):
        codes = padded(units, ds, dl, start_abs, end_abs, total_bits,
                       max_len, n_out)
        return _epilogue(codes, n_out, opos, oval, eb, radius, shape,
                         out_dtype)

    return DecodeBackend(name="ref", count_fn=count, sync_fn=sync,
                         tiles_fn=hd.decode_write_tiles, padded_fn=padded,
                         fused_tiles_fn=fused_tiles,
                         fused_padded_fn=fused_padded)


def _make_pallas_backend() -> DecodeBackend:
    """Kernel backend: the Pallas kernels of ``repro.kernels``, compiled on
    a TPU and run by the Pallas interpreter elsewhere
    (``kernels.common.use_interpreter`` decides, nothing else)."""
    from repro.kernels import ops  # lazy: keeps core jnp-only by default

    def count(units, ds, dl, start_abs, end_abs, total_bits, max_len):
        counts, _ = ops.subseq_counts(units, ds, dl, start_abs, end_abs,
                                      total_bits, max_len)
        return counts

    def sync(units, ds, dl, total_bits, n_subseq, sps, max_len,
             early_exit=True):
        start, counts, _ = ops.selfsync_sync(units, ds, dl, total_bits,
                                             n_subseq, sps, max_len,
                                             early_exit=early_exit)
        return start, counts

    def padded(units, ds, dl, start_abs, end_abs, total_bits, max_len, n_out):
        out, _ = ops.decode_padded_compact(units, ds, dl, start_abs, end_abs,
                                           total_bits, max_len, n_out)
        return out

    return DecodeBackend(
        name="pallas", count_fn=count, sync_fn=sync,
        tiles_fn=ops.decode_write_tiles, padded_fn=padded,
        fused_tiles_fn=ops.decode_write_tiles_fused,
        fused_padded_fn=ops.decode_padded_fused)


register_backend("ref", _make_ref_backend)
register_backend("pallas", _make_pallas_backend)


# ---------------------------------------------------------------------------
# Encode-side backend registry (the write-path twin of the decode registry)
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class EncodeBackend:
    """One implementation of the encode phases (quantize/histogram/bit-pack).

    ``device=True`` backends keep the full-size arrays resident: quantize
    runs in-graph (f32), the histogram kernel reduces the codes on device,
    and the only host transfer before the bit-pack dispatch is the
    ``2*radius``-entry histogram (codebook construction is host numpy --
    the ISSUE-sanctioned small transfer).  The "ref" backend is the host
    path (f64 prequantization + numpy histogram), kept as the storage-grade
    oracle.

    ``quantize_fn``  (x, abs_eb, radius) -> (codes u16, outlier bool,
                     residual i32), shapes matching ``x``
    ``hist_fn``      (codes, nbins) -> int32[nbins]
    ``pack_fn``      (symbols, enc_code, enc_len, total_bits, sps, min_len)
                     -> ``EncodedStream``

    Every bit-pack launch is counted in ``stats["encode_dispatches"]``;
    compress requests a device backend cannot serve (non-float32 inputs)
    fall back to the host path, counted in ``stats["encode_fallbacks"]``,
    never wrong.
    """

    name: str
    device: bool
    quantize_fn: Callable
    hist_fn: Callable
    pack_fn: Callable
    stats: dict = dataclasses.field(
        default_factory=lambda: {"encode_dispatches": 0,
                                 "encode_fallbacks": 0,
                                 "encoder_plan_builds": 0})
    _stats_lock: threading.Lock = dataclasses.field(
        default_factory=threading.Lock, repr=False, compare=False)

    def bump(self, key: str, n: int = 1):
        """Atomic counter increment (see ``DecodeBackend.bump``)."""
        with self._stats_lock:
            self.stats[key] += n

    def reset_stats(self):
        with self._stats_lock:
            for k in self.stats:
                self.stats[k] = 0

    def pack(self, symbols, enc_code, enc_len, total_bits, sps, min_len):
        self.bump("encode_dispatches")
        return self.pack_fn(symbols, enc_code, enc_len, total_bits, sps,
                            min_len)


_ENCODE_FACTORIES: dict[str, Callable[[], EncodeBackend]] = {}
_ENCODE_BACKENDS: dict[str, EncodeBackend] = {}


def register_encode_backend(name: str, factory: Callable[[], EncodeBackend]):
    """Register (or replace) an encode backend under ``name`` (lazy factory,
    same contract as :func:`register_backend`)."""
    _ENCODE_FACTORIES[name] = factory
    _ENCODE_BACKENDS.pop(name, None)


def available_encode_backends() -> list[str]:
    return sorted(_ENCODE_FACTORIES)


def get_encode_backend(backend: "str | EncodeBackend") -> EncodeBackend:
    if isinstance(backend, EncodeBackend):
        return backend
    if backend not in _ENCODE_FACTORIES:
        raise ValueError(f"unknown encode backend {backend!r}; available: "
                         f"{available_encode_backends()}")
    if backend not in _ENCODE_BACKENDS:
        _ENCODE_BACKENDS[backend] = _ENCODE_FACTORIES[backend]()
    return _ENCODE_BACKENDS[backend]


def _host_quantize(x, abs_eb, radius):
    from repro.core.sz import lorenzo  # lazy: core.sz imports this module

    return lorenzo.quantize_host(np.asarray(x), abs_eb, radius=radius)


def _jnp_quantize(x, abs_eb, radius):
    from repro.core.sz import lorenzo

    return lorenzo.quantize(jnp.asarray(x), abs_eb, radius=radius)


def _ref_pack(symbols, enc_code, enc_len, total_bits, sps, min_len):
    del min_len  # only sizes the gather/kernel lane budgets
    from repro.core.huffman import encode as he

    symbols = jnp.asarray(symbols)
    if symbols.shape[0] == 0:
        return he.empty_stream(sps)
    return he._encode_padded(symbols, jnp.asarray(enc_code),
                             jnp.asarray(enc_len),
                             n_units_padded=he.units_for_bits(total_bits, sps),
                             subseqs_per_seq=sps)


def _gather_pack(symbols, enc_code, enc_len, total_bits, sps, min_len):
    from repro.core.huffman import encode as he

    return he.encode_gather(jnp.asarray(symbols), enc_code, enc_len,
                            total_bits, subseqs_per_seq=sps, min_len=min_len)


@functools.partial(jax.jit, static_argnames=("nbins", "chunk"))
def _sorted_histogram(codes, nbins: int, chunk: int = 4096):
    """Exact histogram via chunked sort + per-row edge searchsorted.

    XLA lowers a scatter-add histogram (``jnp.bincount``) to a serial
    scatter; sorting fixed-size rows and differencing the edge positions is
    the same O(n) answer built from primitives that vectorize.  Rows are
    padded with ``nbins`` (an out-of-range key) so the tail never perturbs
    a real bin.
    """
    flat = codes.reshape(-1).astype(jnp.int32)
    pad = (-flat.shape[0]) % chunk
    rows = jnp.pad(flat, (0, pad), constant_values=nbins).reshape(-1, chunk)
    rows = jnp.sort(rows, axis=1)
    edges = jnp.arange(nbins + 1, dtype=jnp.int32)
    cuts = jax.vmap(lambda r: jnp.searchsorted(r, edges, side="left"))(rows)
    return (cuts[:, 1:] - cuts[:, :-1]).sum(axis=0).astype(jnp.int32)


def _make_ref_encode_backend() -> EncodeBackend:
    """The current host path: f64 prequantization, numpy histogram, and the
    jit bit materialization sized from a host pass over the symbols."""
    def hist(codes, nbins):
        return np.bincount(np.asarray(codes).reshape(-1), minlength=nbins)

    return EncodeBackend(name="ref", device=False,
                         quantize_fn=_host_quantize, hist_fn=hist,
                         pack_fn=_ref_pack)


def _make_jnp_encode_backend() -> EncodeBackend:
    """Device-resident pure-jnp pipeline: in-graph f32 quantize, sorted
    device histogram, and the per-unit gather bit-pack -- sized from the
    histogram, so no full-size array crosses to host (the timeable device
    proxy of the kernel backends, exactly like "ref" on the decode side)."""
    return EncodeBackend(name="jnp", device=True, quantize_fn=_jnp_quantize,
                         hist_fn=_sorted_histogram, pack_fn=_gather_pack)


def _make_pallas_encode_backend() -> EncodeBackend:
    """Kernel backend: Lorenzo quantize + histogram + bit-pack kernels
    (compiled on a TPU, interpreted elsewhere)."""
    from repro.kernels import ops  # lazy: keeps core jnp-only by default

    def quantize(x, abs_eb, radius):
        x = jnp.asarray(x)
        if x.ndim == 1:
            return ops.lorenzo_quantize(x, abs_eb, radius=radius)
        return _jnp_quantize(x, abs_eb, radius)

    def pack(symbols, enc_code, enc_len, total_bits, sps, min_len):
        return ops.encode_bitpack(symbols, enc_code, enc_len, total_bits,
                                  sps, min_len=min_len)

    return EncodeBackend(name="pallas", device=True, quantize_fn=quantize,
                         hist_fn=ops.histogram, pack_fn=pack)


register_encode_backend("ref", _make_ref_encode_backend)
register_encode_backend("jnp", _make_jnp_encode_backend)
register_encode_backend("pallas", _make_pallas_encode_backend)


@dataclasses.dataclass
class EncoderPlan:
    """Everything the bit-pack dispatch needs, sized without touching the
    symbol array: the canonical codebook (host package-merge over the
    histogram), its tables as device arrays, and the exact payload size
    ``total_bits = sum(freq * code_lengths)`` -- so a device backend's only
    pre-pack host transfer is the ``2*radius``-entry histogram."""

    codebook: Any               # core.huffman.codebook.Codebook
    enc_code: jnp.ndarray       # uint32[K] on device
    enc_len: jnp.ndarray        # uint8[K] on device
    total_bits: int
    subseqs_per_seq: int

    @property
    def min_len(self) -> int:
        return self.codebook.min_len


def build_encoder_plan(freq, max_len: int, subseqs_per_seq: int,
                       backend: "str | EncodeBackend" = "ref") -> EncoderPlan:
    """Histogram -> canonical length-limited codebook -> placement sizes.

    ``freq`` may live on device; the host transfer of these ``2*radius``
    counts is the entire host involvement of a device-backend encode (the
    package-merge length limiting stays numpy, as the ISSUE sanctions).
    Counted in ``backend.stats["encoder_plan_builds"]``.
    """
    from repro.core.huffman import codebook as cb

    be = get_encode_backend(backend)
    be.bump("encoder_plan_builds")
    freq_np = np.asarray(freq, dtype=np.int64)
    book = cb.build_codebook(freq_np, max_len=max_len)
    total_bits = int((freq_np * book.enc_len.astype(np.int64)).sum())
    return EncoderPlan(codebook=book,
                       enc_code=jnp.asarray(book.enc_code),
                       enc_len=jnp.asarray(book.enc_len),
                       total_bits=total_bits,
                       subseqs_per_seq=subseqs_per_seq)


def encode_with_plan(symbols, plan: EncoderPlan,
                     backend: "str | EncodeBackend" = "ref") -> EncodedStream:
    """Bit-pack ``symbols`` through ``backend`` under a prebuilt plan.

    The emitted ``EncodedStream`` layout is identical across backends
    (asserted bit-exact by the encode parity matrix in tests), so decode
    never knows which backend wrote the bytes.
    """
    be = get_encode_backend(backend)
    return be.pack(symbols, plan.enc_code, plan.enc_len, plan.total_bits,
                   plan.subseqs_per_seq, plan.min_len)


# ---------------------------------------------------------------------------
# Plan construction (phases 1-3 + classification)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class DecodeLuts:
    """Minimal decode-table view: what ``decode()`` needs of a Codebook."""

    dec_sym: Any
    dec_len: Any
    max_len: int


def _as_luts(codebook) -> DecodeLuts:
    return DecodeLuts(dec_sym=jnp.asarray(codebook.dec_sym),
                      dec_len=jnp.asarray(codebook.dec_len),
                      max_len=int(codebook.max_len))


@dataclasses.dataclass
class DecoderPlan:
    """Everything phase 4 needs: sync starts, counts, offsets, CR classes."""

    method: str                 # "gap" | "selfsync"
    start_bits: jnp.ndarray     # int32[n_subseq] absolute sync starts
    end_bits: jnp.ndarray       # int32[n_subseq] absolute window ends
    counts: jnp.ndarray         # int32[n_subseq] codeword starts per window
    offsets: jnp.ndarray        # int32[n_subseq+1] exclusive prefix sum
    seq_counts: np.ndarray      # int64[n_seq] symbols per sequence
    classes: ClassPlan          # per-CR-class dispatch plan
    subseqs_per_seq: int
    t_high: int


@functools.partial(jax.jit, static_argnames=("n_subseq",))
def _window_bounds(n_subseq: int):
    """First bit of every 128-bit window, and of the window after it."""
    b = jnp.arange(n_subseq, dtype=jnp.int32) * SUBSEQ_BITS
    return b, b + SUBSEQ_BITS


@jax.jit
def _gap_starts(boundaries, gaps):
    """Sync start of every window: its boundary plus the stored gap,
    clamped to the window."""
    return boundaries + jnp.minimum(gaps.astype(jnp.int32), SUBSEQ_BITS)


def build_plan(stream: EncodedStream, codebook, method: str = "gap",
               backend: "str | DecodeBackend" = "ref",
               t_high: int = T_HIGH_DEFAULT,
               early_exit: bool = True) -> DecoderPlan:
    """Run decode phases 1-3 on ``backend`` and classify sequences by CR.

    Phase 1-2 discovers the per-subsequence sync points -- from the stored
    gap array (``method="gap"``) or by self-synchronization
    (``method="selfsync"``, with ``early_exit`` controlling the paper's
    ``__all_sync`` round termination) -- and counts the codewords per
    128-bit window; phase 3 prefix-sums the counts into output offsets.
    The per-sequence symbol counts then feed the online tuner (paper
    Alg. 2): sequences are classified by compression ratio into classes
    ``1..t_high+1`` and sorted into the per-class dispatch lists of
    ``ClassPlan``.

    The returned ``DecoderPlan`` is backend-portable (device arrays plus
    host metadata, no backend handles) and content-addressable: the
    ``Codec`` / store layers cache plans keyed by payload digest, and
    every build is counted in ``backend.stats["plan_builds"]`` so tests
    and benchmarks can assert cache hits.
    """
    be = get_backend(backend)
    be.bump("plan_builds")
    problems = _cb.validate_codebook(codebook)
    if problems:
        be.bump("decode_guard_trips")
        raise DecodeGuardError("corrupt codebook rejected at build_plan: "
                               + "; ".join(problems))
    luts = _as_luts(codebook)
    units = jnp.asarray(stream.units)
    n_subseq = stream.n_subseq
    sps = stream.subseqs_per_seq
    boundaries, ends = _window_bounds(n_subseq)

    if method == "gap":
        # A valid gap never exceeds SUBSEQ_BITS (the encoder stores the
        # offset of the first codeword start inside a 128-bit window, or
        # the in-window distance to end-of-stream).  ``_gap_starts`` clamps
        # anything larger -- a corrupt gap array -- so sync starts stay
        # inside the window their counts were computed for; count the
        # containment.
        if stream.gaps.size and int(np.asarray(stream.gaps).max(
                initial=0)) > SUBSEQ_BITS:
            be.bump("decode_guard_trips")
        starts = _gap_starts(boundaries, stream.gaps)
        counts = be.count_fn(units, luts.dec_sym, luts.dec_len, starts, ends,
                             stream.total_bits, luts.max_len)
    elif method == "selfsync":
        starts, counts = be.sync_fn(units, luts.dec_sym, luts.dec_len,
                                    stream.total_bits, n_subseq, sps,
                                    luts.max_len, early_exit=early_exit)
    else:
        raise ValueError(f"unknown method {method!r}; valid methods: "
                         f"{list(VALID_PLAN_METHODS)}")

    counts = jnp.asarray(counts)
    offsets = hd.output_offsets(counts)
    seq_counts = np.asarray(counts).reshape(-1, sps).sum(
        axis=1, dtype=np.int64)
    classes = make_plan(None, seq_counts, sps, t_high)
    return DecoderPlan(method=method, start_bits=jnp.asarray(starts),
                       end_bits=ends, counts=counts, offsets=offsets,
                       seq_counts=seq_counts, classes=classes,
                       subseqs_per_seq=sps, t_high=t_high)


# ---------------------------------------------------------------------------
# Execution (phase 4)
# ---------------------------------------------------------------------------


def _pad_pow2(n: int, lo: int = 8) -> int:
    p = lo
    while p < n:
        p *= 2
    return p


def _max_tile_span(offsets: np.ndarray, tile_syms: int, n_sym: int) -> int:
    """Most subsequences any ``tile_syms``-symbol output tile overlaps.

    ``offsets`` is the exclusive prefix sum over the gathered subsequences
    (host int64).  Matches the ``searchsorted`` tile->subsequence mapping of
    the decode-write kernels.
    """
    if n_sym <= 0 or offsets.shape[0] <= 1:
        return 1
    n_tiles = (n_sym + tile_syms - 1) // tile_syms
    base = np.arange(n_tiles, dtype=np.int64) * tile_syms
    s0 = np.searchsorted(offsets, base, side="right") - 1
    last = np.minimum(base + tile_syms, n_sym) - 1
    s1 = np.maximum(np.searchsorted(offsets, last, side="right") - 1, s0)
    return int((s1 - s0 + 1).max())


def _class_dispatch(tiles_fn, units, dec_sym, dec_len, max_len: int,
                    total_bits, tensors: list, t_high: int) -> list:
    """Per-CR-class decode-write over one or many tensors.

    ``tensors`` holds one dict per decoded tensor:
      starts / ends / counts : int32[n_seq * sps] (bit positions already
                               shifted into the merged unit space)
      sps                    : subsequences per sequence
      seq_counts             : int64[n_seq] (host)
      seq_out_start          : int64[n_seq+1] global output offsets (host)
      classes                : ClassPlan
      lut_base               : int or None -- offset into the merged LUT
      n_out                  : output symbol count

    For every class, the matching sequences of ALL tensors are gathered into
    ONE ``tiles_fn`` dispatch (this is the batching the cuSZ+ line of work
    gets from per-class kernel launches); class-local output is then
    scattered back to each tensor's global positions.
    """
    outs = [jnp.zeros((m["n_out"],), jnp.uint16) for m in tensors]
    use_lut_base = any(m["lut_base"] is not None for m in tensors)

    for c in range(1, t_high + 2):
        sel = []                     # (tensor index, seq ids of class c)
        class_n = 0
        for ti, m in enumerate(tensors):
            ids = m["classes"].class_seq_ids(c)
            if ids.size:
                sel.append((ti, ids))
                class_n += int(m["seq_counts"][ids].sum())
        if not sel:
            continue

        tile = tile_for_class(c, t_high)
        class_n_pad = _pad_pow2(max(class_n, 1))

        # Gather the class's subsequences, DROPPING count-0 lanes (the
        # zero-padded tail of each tensor's final sequence).  Dead lanes
        # carry no symbols but would consume tile-decode lanes: a tile's
        # symbol range could then span more subsequences than ``ss_max``
        # provisions, silently dropping the symbols past the lane budget.
        starts_p, ends_p, counts_p, lut_p = [], [], [], []
        for ti, ids in sel:
            m = tensors[ti]
            sps = m["sps"]
            cnt_rows = m["counts_np"].reshape(-1, sps)[ids].reshape(-1)
            keep = jnp.asarray(np.nonzero(cnt_rows > 0)[0].astype(np.int32))
            row = jnp.asarray(ids, jnp.int32)
            starts_p.append(m["starts"].reshape(-1, sps)[row].reshape(-1)[keep])
            ends_p.append(m["ends"].reshape(-1, sps)[row].reshape(-1)[keep])
            counts_p.append(cnt_rows[cnt_rows > 0])
            if use_lut_base:
                lut_p.append(np.full(counts_p[-1].shape[0],
                                     m["lut_base"] or 0, np.int32))
        g_counts_np = np.concatenate(counts_p).astype(np.int64)
        # Pad the gathered subsequence set and the class output to powers of
        # two so the jit cache stays bounded across class populations.
        n_ss = g_counts_np.shape[0]
        n_ss_pad = _pad_pow2(n_ss)
        pad = n_ss_pad - n_ss
        if pad:
            # Inactive pad lanes: start == end == 0 decodes nothing, zero
            # counts keep the offsets flat past the real output.
            z = jnp.zeros((pad,), jnp.int32)
            starts_p.append(z)
            ends_p.append(z)
            if use_lut_base:
                lut_p.append(np.zeros((pad,), np.int32))
        g_starts = jnp.concatenate(starts_p)
        g_ends = jnp.concatenate(ends_p)
        offs_np = np.zeros(n_ss_pad + 1, np.int64)
        offs_np[1:1 + n_ss] = np.cumsum(g_counts_np)
        offs_np[1 + n_ss:] = offs_np[n_ss]
        g_offsets = jnp.asarray(offs_np.astype(np.int32))

        # Lane provisioning: the static bound assumes every subsequence in a
        # tile's span carries >= min_starts codewords; the (at most one per
        # tensor) partial subsequence at a stream tail can carry fewer, so
        # also bound by the worst ACTUAL span any tile needs.
        ss_max = max(ss_max_for_tile(tile, max_len),
                     _max_tile_span(offs_np[:1 + n_ss], tile, class_n) + 2)
        ss_max = -(-ss_max // 8) * 8   # round up: bounds jit-cache variants

        kwargs = {}
        if use_lut_base:
            kwargs["lut_base"] = jnp.asarray(np.concatenate(lut_p))

        class_out = tiles_fn(units, dec_sym, dec_len, g_starts, g_ends,
                             g_offsets, total_bits, max_len, class_n_pad,
                             tile, ss_max, **kwargs)

        # Scatter class-local output back to each tensor's global positions.
        base = 0
        for ti, ids in sel:
            m = tensors[ti]
            cnt, sos = m["seq_counts"], m["seq_out_start"]
            n_t = int(cnt[ids].sum())
            if n_t:
                pos = np.concatenate([
                    np.arange(sos[s], sos[s] + cnt[s], dtype=np.int64)
                    for s in ids])
                outs[ti] = outs[ti].at[jnp.asarray(pos)].set(
                    class_out[base:base + n_t])
            base += n_t
    return outs


def _tensor_meta(plan: DecoderPlan, n_out: int, bit_offset: int = 0,
                 lut_base: "int | None" = None, clamp_bits=None) -> dict:
    """Phase-4 view of one tensor for ``_class_dispatch``."""
    starts = plan.start_bits
    ends = plan.end_bits
    if clamp_bits is not None:
        ends = jnp.minimum(ends, jnp.int32(clamp_bits))
    if bit_offset:
        starts = starts + jnp.int32(bit_offset)
        ends = ends + jnp.int32(bit_offset)
    seq_out_start = np.zeros(plan.seq_counts.shape[0] + 1, np.int64)
    seq_out_start[1:] = np.cumsum(plan.seq_counts)
    return {
        "starts": starts, "ends": ends,
        "counts_np": np.asarray(plan.counts),
        "sps": plan.subseqs_per_seq, "seq_counts": plan.seq_counts,
        "seq_out_start": seq_out_start, "classes": plan.classes,
        "lut_base": lut_base, "n_out": n_out,
    }


def decode(stream: EncodedStream, codebook, n_out: int, *,
           plan: "DecoderPlan | None" = None,
           backend: "str | DecodeBackend" = "ref",
           method: str = "gap", strategy: str = "tile",
           tile_syms: int = DEFAULT_TILE_SYMS,
           t_high: int = T_HIGH_DEFAULT,
           early_exit: bool = True,
           transform: "OutputTransform | None" = None) -> jnp.ndarray:
    """Decode one stream: the single entry point for every decoder variant.

    Args:
      stream:    the ``EncodedStream`` to decode.
      codebook:  anything with ``dec_sym`` / ``dec_len`` / ``max_len``
                 decode tables (normally a ``Codebook``).
      n_out:     number of symbols to emit.
      plan:      a prebuilt ``DecoderPlan`` (phases 1-3).  Plans are
                 backend-portable -- one built on "ref" executes exactly on
                 "pallas" and vice versa.  ``None`` builds one here with
                 ``method``.
      backend:   a registered backend name (``available_backends()``) or a
                 ``DecodeBackend`` handle.
      method:    sync discovery when building the plan: "gap" (gap array)
                 or "selfsync" (see ``VALID_PLAN_METHODS``).
      strategy:  decode-write variant: "tuned" (per-CR-class tiles, paper
                 Alg. 2), "tile" (fixed ``tile_syms`` tiles, Alg. 1), or
                 "padded" (the original decoders' baseline layout).
      tile_syms: tile size for the fixed-"tile" strategy.
      t_high:    highest non-overflow CR class when building the plan.
      early_exit: the self-sync ``__all_sync`` early-exit toggle.
      transform: optional ``OutputTransform``.  When attached, phase 4 runs
                 the backend's FUSED ops: the decoded symbols are carried
                 through dequantization and the inverse-Lorenzo prefix sum
                 inside the decode-write dispatch and the return value is
                 the reconstructed array, flat in C-order (the uint16
                 quant-code array is never materialized).  The transform's
                 ``shape`` picks the 1-D or N-D reconstruction and
                 ``out_dtype`` the output precision (f32 compute, one final
                 cast).  Supported for the "tile" and "padded" strategies
                 on backends registered with fused ops; the "tuned"
                 strategy gathers sequences by CR class, which reorders the
                 output and breaks the sequential reconstruction carry, so
                 it raises ``ValueError`` (callers such as
                 ``sz.compressor.decompress`` fall back to the two-pass
                 path and count ``stats["fused_fallbacks"]``).

    Returns uint16[n_out] quant codes, or reconstructed ``out_dtype[n_out]``
    when ``transform`` is attached.
    """
    be = get_backend(backend)
    luts = _as_luts(codebook)
    if plan is None:
        plan = build_plan(stream, codebook, method=method, backend=be,
                          t_high=t_high, early_exit=early_exit)
    units = jnp.asarray(stream.units)

    if transform is not None and strategy in ("tile", "padded"):
        if not be.supports_fused:
            raise ValueError(
                f"backend {be.name!r} registers no fused ops; check "
                f"backend.supports_fused before attaching a transform")
        t = transform
        t_shape = tuple(t.shape) if t.shape is not None else None
        t_dtype = (jnp.dtype(t.out_dtype) if t.out_dtype is not None
                   else jnp.float32)
        if strategy == "padded":
            return be.decode_padded_fused(
                units, luts.dec_sym, luts.dec_len, plan.start_bits,
                plan.end_bits, stream.total_bits, luts.max_len, n_out,
                t.outlier_pos, t.outlier_val, t.eb, t.radius,
                shape=t_shape, out_dtype=t_dtype)
        ss_max = ss_max_for_tile(tile_syms, luts.max_len)
        return be.decode_tiles_fused(
            units, luts.dec_sym, luts.dec_len, plan.start_bits,
            plan.end_bits, plan.offsets, stream.total_bits, luts.max_len,
            n_out, tile_syms, ss_max, t.outlier_pos, t.outlier_val, t.eb,
            t.radius, shape=t_shape, out_dtype=t_dtype)
    if transform is not None and strategy in VALID_STRATEGIES:
        raise ValueError(
            f"fused decode (transform=) supports strategies 'tile' and "
            f"'padded', not {strategy!r}: the tuned per-CR-class gather "
            f"reorders the output, which breaks the sequential Lorenzo "
            f"reconstruction carry")

    if strategy == "padded":
        return be.decode_padded(units, luts.dec_sym, luts.dec_len,
                                plan.start_bits, plan.end_bits,
                                stream.total_bits, luts.max_len, n_out)
    if strategy == "tile":
        ss_max = ss_max_for_tile(tile_syms, luts.max_len)
        return be.decode_tiles(units, luts.dec_sym, luts.dec_len,
                               plan.start_bits, plan.end_bits, plan.offsets,
                               stream.total_bits, luts.max_len, n_out,
                               tile_syms, ss_max)
    if strategy == "tuned":
        meta = _tensor_meta(plan, n_out)
        return _class_dispatch(be.decode_tiles, units, luts.dec_sym,
                               luts.dec_len, luts.max_len, stream.total_bits,
                               [meta], plan.t_high)[0]
    raise ValueError(f"unknown strategy {strategy!r}; valid strategies: "
                     f"{list(VALID_STRATEGIES)}")


def execute_tuned(stream: EncodedStream, dec_sym, dec_len, max_len: int,
                  n_out: int, start_bits, counts,
                  t_high: int = T_HIGH_DEFAULT, tiles_fn=None) -> jnp.ndarray:
    """Tuned per-class decode from precomputed phase 1-3 outputs.

    Raw-LUT entry point for callers that hold decode tables instead of a
    ``Codebook``: ``tiles_fn`` defaults to the jnp reference tile decoder
    and may be any ``decode_write_tiles``-shaped callable (e.g. the Pallas
    kernel wrapper).
    """
    if tiles_fn is None:
        tiles_fn = hd.decode_write_tiles
    counts = jnp.asarray(counts)
    sps = stream.subseqs_per_seq
    n_subseq = stream.n_subseq
    seq_counts = np.asarray(counts).reshape(-1, sps).sum(axis=1,
                                                         dtype=np.int64)
    classes = make_plan(None, seq_counts, sps, t_high)
    ends = jnp.arange(n_subseq, dtype=jnp.int32) * SUBSEQ_BITS + SUBSEQ_BITS
    plan = DecoderPlan(method="gap", start_bits=jnp.asarray(start_bits),
                       end_bits=ends, counts=counts,
                       offsets=hd.output_offsets(counts),
                       seq_counts=seq_counts, classes=classes,
                       subseqs_per_seq=sps, t_high=t_high)
    meta = _tensor_meta(plan, n_out)
    return _class_dispatch(tiles_fn, jnp.asarray(stream.units), dec_sym,
                           dec_len, max_len, stream.total_bits, [meta],
                           t_high)[0]


# ---------------------------------------------------------------------------
# Batched multi-tensor decode
# ---------------------------------------------------------------------------


def _merge_luts(codebooks) -> tuple:
    """Stack per-tensor decode LUTs into one table at a common ``max_len``.

    A tensor whose codebook peeks fewer bits than the global maximum gets
    its LUT upsampled: window ``w`` at ``max_len_g`` bits resolves via the
    top ``max_len_t`` bits, i.e. ``np.repeat`` by the width ratio.  Huffman
    codes are prefix-free, so the extra peeked bits never change the decoded
    (symbol, length) pair.
    """
    max_len_g = max(int(cb.max_len) for cb in codebooks)
    syms, lens, bases = [], [], []
    stride = 1 << max_len_g
    for t, cb in enumerate(codebooks):
        reps = 1 << (max_len_g - int(cb.max_len))
        syms.append(np.repeat(np.asarray(cb.dec_sym), reps))
        lens.append(np.repeat(np.asarray(cb.dec_len), reps))
        bases.append(t * stride)
    return (jnp.asarray(np.concatenate(syms)),
            jnp.asarray(np.concatenate(lens)), max_len_g, bases)


# Bit positions are int32 throughout the decode stack; keep every merged
# stream comfortably inside that space (one chunk still decode-batches
# hundreds of tensors -- 2^30 bits is 128 MiB of compressed payload).
MAX_BATCH_BITS = 1 << 30


def decode_batch(streams, codebooks, n_outs, *,
                 plans=None, backend: "str | DecodeBackend" = "ref",
                 method: str = "gap", t_high: int = T_HIGH_DEFAULT,
                 early_exit: bool = True) -> list:
    """Decode many tensors with one decode-write dispatch per CR class.

    Streams are concatenated at subsequence granularity (every stream is
    already padded to whole sequences), LUTs are merged at a common
    ``max_len`` with a per-subsequence ``lut_base``, and phase 4 gathers
    same-class sequences from ALL tensors into one tile-decode dispatch.
    Phases 1-3 remain per-tensor (they are the cheap, bandwidth-bound
    phases; the dispatch-bound phase is decode-write).

    Batches whose merged bitstream would overflow the int32 bit-position
    space are transparently split into sub-batches of at most
    ``MAX_BATCH_BITS`` merged bits (dispatch count then scales with the
    number of sub-batches, not with the tensor count).

    Returns a list of uint16 symbol arrays, bit-exact with per-tensor
    ``decode()``.  This entry point always emits quant codes; the fused
    decode→dequantize→reconstruct path is per-tensor by construction (its
    reconstruction carry follows one tensor's output order), so
    ``sz.compressor.decompress_batch(fused=True)`` routes eligible tensors
    through per-tensor fused decodes and only the remainder through this
    class-merged path.
    """
    items = list(zip(streams, codebooks, n_outs))
    if not items:
        return []
    be = get_backend(backend)
    if plans is None:
        plans = [build_plan(s, cb, method=method, backend=be, t_high=t_high,
                            early_exit=early_exit)
                 for s, cb, _ in items]

    # Split oversized multi-tensor batches.  A SINGLE stream over the budget
    # is never split (it is the base case): it decodes alone, subject to the
    # same int32 bit-position ceiling as every per-tensor decode.
    item_bits = [int(s.units.shape[0]) * UNIT_BITS for s in streams]
    if len(items) > 1 and sum(item_bits) > MAX_BATCH_BITS:
        outs, lo, acc = [], 0, 0
        for i, b in enumerate(item_bits):
            if acc and acc + b > MAX_BATCH_BITS:
                outs += decode_batch(streams[lo:i], codebooks[lo:i],
                                     n_outs[lo:i], plans=plans[lo:i],
                                     backend=be, t_high=t_high)
                lo, acc = i, 0
            acc += b
        outs += decode_batch(streams[lo:], codebooks[lo:], n_outs[lo:],
                             plans=plans[lo:], backend=be, t_high=t_high)
        return outs

    dec_sym, dec_len, max_len_g, lut_bases = _merge_luts(codebooks)

    unit_arrays = [jnp.asarray(s.units) for s in streams]
    units = jnp.concatenate(unit_arrays)
    bit_offsets = np.zeros(len(items), np.int64)
    bit_offsets[1:] = np.cumsum(
        [int(u.shape[0]) * UNIT_BITS for u in unit_arrays])[:-1]
    merged_total_bits = jnp.int32(int(units.shape[0]) * UNIT_BITS)

    metas = []
    for t, ((stream, _cb, n_out), plan) in enumerate(zip(items, plans)):
        # Windows must clamp at the *tensor's* payload end before shifting
        # into the merged bit space (the merged total no longer clamps them).
        metas.append(_tensor_meta(plan, n_out,
                                  bit_offset=int(bit_offsets[t]),
                                  lut_base=lut_bases[t],
                                  clamp_bits=stream.total_bits))
    return _class_dispatch(be.decode_tiles, units, dec_sym, dec_len,
                           max_len_g, merged_total_bits, metas, t_high)
