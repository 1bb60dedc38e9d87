"""jit'd wrappers around the Pallas kernels + the full kernel decode pipeline.

Everything here mirrors a function in ``repro.kernels.ref`` (the pure-jnp
oracle); tests sweep shapes/dtypes and assert exact equality.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.core.huffman import decode as hd
from repro.core.huffman import encode as he
from repro.core.huffman.bits import SUBSEQ_BITS
from repro.kernels import common as C
from repro.kernels import fused_decode as _fus
from repro.kernels import histogram as _hist
from repro.kernels import huffman_decode as _dec
from repro.kernels import huffman_encode as _enc
from repro.kernels import huffman_selfsync as _sync
from repro.kernels import lorenzo as _lor

# ---------------------------------------------------------------------------
# Metadata prep shared by the decode kernels
# ---------------------------------------------------------------------------


def _subseq_windows(start_abs, end_abs, total_bits):
    """Convert absolute bit windows to (subseq_id, row-local start/end)."""
    start_abs = start_abs.astype(jnp.int32)
    ids = start_abs // SUBSEQ_BITS
    base = ids * SUBSEQ_BITS
    start_local = start_abs - base
    end_local = jnp.clip(jnp.minimum(end_abs, total_bits) - base, 0,
                         C.ROW_UNITS * 32)
    return ids, start_local, end_local


def _lane_block(ids, start_local, end_local, block_rows: int):
    """Pad per-subsequence lane arrays to whole ``block_rows * 128`` blocks
    and lay them out as ``(rows, 128)``; pad lanes have an empty window."""
    n = ids.shape[0]
    pad = (-n) % (block_rows * C.LANES) or (0 if n else block_rows * C.LANES)
    z = jnp.zeros((pad,), jnp.int32)
    ids, start_local, end_local = (jnp.concatenate([a, z]).reshape(-1, C.LANES)
                                   for a in (ids, start_local, end_local))
    return ids, start_local, end_local


@partial(jax.jit, static_argnames=("max_len",))
def subseq_counts(units, dec_sym, dec_len, start_abs, end_abs, total_bits,
                  max_len: int):
    """Kernel-backed phase 1: codeword counts and landing positions."""
    ids, start_local, end_local = _subseq_windows(start_abs, end_abs,
                                                  total_bits)
    n = ids.shape[0]
    ids, start_local, end_local = _lane_block(ids, start_local, end_local,
                                              _dec.DEFAULT_BLOCK_ROWS)
    rows = C.lane_rows(units, ids)
    counts, landing = _dec.count_subseq(rows, start_local, end_local,
                                        C.pack_lut(dec_sym, dec_len), max_len,
                                        int(dec_sym.shape[0]))
    return counts.reshape(-1)[:n], landing.reshape(-1)[:n]


def tile_inputs(units, start_bits, end_bits, offsets, total_bits,
                n_out: int, tile_syms: int, ss_max: int, lut_base=None,
                outlier_pos=None) -> _dec.TileInputs:
    """Per-tile lane metadata shared by the plain and fused tile decoders.

    Maps each output tile to the (statically bounded) range of subsequences
    overlapping it and converts their absolute bit windows to row-local
    coordinates.  ``offsets`` is the exclusive prefix sum of the per-window
    symbol counts (``n_subseq + 1`` entries).  With ``outlier_pos`` (the
    -1-padded side list) every tile's ``meta`` row ends with the range of
    the side list inside the tile.
    """
    n_subseq = start_bits.shape[0]
    n_tiles = (n_out + tile_syms - 1) // tile_syms
    tile_base = jnp.arange(n_tiles, dtype=jnp.int32) * tile_syms
    s0 = jnp.clip(jnp.searchsorted(offsets, tile_base, side="right") - 1,
                  0, n_subseq - 1)

    n_lanes = C.lane_block(ss_max)
    lane = jnp.arange(n_lanes, dtype=jnp.int32)
    subs_raw = s0[:, None] + lane[None, :]
    valid = (subs_raw < n_subseq) & (lane < ss_max)[None, :]
    subs = jnp.clip(subs_raw, 0, n_subseq - 1)

    ids, start_local, end_local = _subseq_windows(
        start_bits[subs], end_bits[subs], total_bits)
    # Invalid (clipped) lanes: no work, no symbols.
    start_local = jnp.where(valid, start_local, 0)
    end_local = jnp.where(valid, end_local, 0)
    offsets = jnp.asarray(offsets, jnp.int32)
    off_local = jnp.where(valid, offsets[subs] - tile_base[:, None], 0)
    count = jnp.where(valid, offsets[subs + 1] - offsets[subs], 0)
    if lut_base is None:
        lut_tile = jnp.zeros(subs.shape, jnp.int32)
    else:
        lut_tile = jnp.where(valid, lut_base[subs], 0).astype(jnp.int32)

    meta = [off_local, count]
    if outlier_pos is not None:
        r = C.outlier_ranges(outlier_pos,
                             jnp.append(tile_base, n_tiles * tile_syms))
        meta.append(jnp.stack([r[:-1], r[1:]], axis=1))
    lanes = (n_tiles, n_lanes // C.LANES, C.LANES)
    return _dec.TileInputs(
        rows=C.lane_rows(units, ids.reshape(lanes)),
        start=start_local.reshape(lanes), end=end_local.reshape(lanes),
        lut_base=lut_tile.reshape(lanes),
        meta=jnp.concatenate(meta, axis=1).astype(jnp.int32)[:, None, :])


@partial(jax.jit, static_argnames=("max_len", "n_out", "tile_syms", "ss_max"))
def decode_write_tiles(units, dec_sym, dec_len, start_bits, end_bits, offsets,
                       total_bits, max_len: int, n_out: int, tile_syms: int,
                       ss_max: int, lut_base=None):
    """Kernel-backed phase 4; signature-compatible with the jnp reference
    ``core.huffman.decode.decode_write_tiles`` (so the tuner can inject it).

    ``lut_base`` (optional int32[n_subseq]) selects a per-subsequence decode
    table inside a merged LUT (the batched multi-tensor path).
    """
    ti = tile_inputs(units, start_bits, end_bits, offsets, total_bits, n_out,
                     tile_syms, ss_max, lut_base)
    tiles = _dec.decode_tiles(ti, C.pack_lut(dec_sym, dec_len), max_len,
                              int(dec_sym.shape[0]), tile_syms, ss_max)
    return tiles.reshape(ti.n_tiles, -1)[:, :tile_syms].reshape(-1)[:n_out]


def _two_eb_f32(eb):
    """The reconstruction scale as a float32[1] kernel input.

    Doubling commutes with float32 rounding (power-of-two scaling), so this
    is bit-identical to the ``2 * eb`` inside ``lorenzo.dequantize``.
    """
    return jnp.asarray(eb, jnp.float32).reshape(1) * 2


def fused_squeeze(shape):
    """Canonical fused-path view of ``shape``: unit axes dropped.

    Cumsum along a unit axis is the identity, so reconstruction over the
    squeezed shape is bitwise the reconstruction over the full shape.  Both
    the eligibility check (``compressor.fused_unsupported_reason``) and the
    kernel dispatch below must agree on this rule.
    """
    if shape is None:
        return None
    sq = tuple(int(s) for s in shape if s != 1)
    return sq if len(sq) > 1 else None


def fused_tile_rows(shape, tile_syms: int) -> int:
    """Rows per tile for the N-D fused kernels.

    ~``tile_syms`` symbols per tile, rounded to whole rows; beyond 2-D the
    row count must divide the plane height so no tile crosses a plane
    boundary (the row-carry reset happens between tiles).
    """
    plane_rows, cols = shape[-2], shape[-1]
    w = max(1, tile_syms // cols)
    w = min(w, plane_rows)
    if len(shape) >= 3:
        while plane_rows % w:
            w -= 1
    return w


@partial(jax.jit, static_argnames=("max_len", "n_out", "tile_syms", "ss_max",
                                   "radius", "shape", "out_dtype"))
def decode_write_tiles_fused(units, dec_sym, dec_len, start_bits, end_bits,
                             offsets, total_bits, max_len: int, n_out: int,
                             tile_syms: int, ss_max: int, opos, oval, eb,
                             radius: int, lut_base=None, shape=None,
                             out_dtype=jnp.float32):
    """Fused phase 4: tile decode + dequantize + inverse-Lorenzo epilogue.

    Same tile mapping as :func:`decode_write_tiles`; the kernel carries the
    decoded symbols through ``2*eb*(cumsum(code - radius))`` (outlier side
    list ``opos``/``oval`` written in) without materializing the quant-code
    array.  ``shape`` selects the N-D epilogue (row carry in VMEM, the
    carries of outer axes in HBM); unit axes are squeezed first, so e.g.
    ``(1, n)`` still takes the 1-D chained-carry kernel.  Returns
    reconstructed ``out_dtype[n_out]`` (flat, C-order).
    """
    sq = fused_squeeze(shape)
    out_dtype = jnp.dtype(out_dtype)
    lut = C.pack_lut(dec_sym, dec_len)
    side = C.side_list(opos, oval)
    if sq is None:
        ti = tile_inputs(units, start_bits, end_bits, offsets, total_bits,
                         n_out, tile_syms, ss_max, lut_base, outlier_pos=opos)
        out = _fus.decode_tiles_fused(
            ti, lut, side, _two_eb_f32(eb), max_len, int(dec_sym.shape[0]),
            tile_syms, ss_max, radius, out_dtype=out_dtype)
        return out.reshape(ti.n_tiles, -1)[:, :tile_syms].reshape(-1)[:n_out]
    # N-D: re-tile along whole rows of the fastest axis.  The tile size
    # changes, so the lane budget must be re-derived for the new tile.
    from repro.core.huffman.pipeline import ss_max_for_tile

    rows_per_tile = fused_tile_rows(sq, tile_syms)
    block = rows_per_tile * sq[-1]
    ss_max_nd = ss_max_for_tile(block, max_len)
    ti = tile_inputs(units, start_bits, end_bits, offsets, total_bits, n_out,
                     block, ss_max_nd, lut_base, outlier_pos=opos)
    out = _fus.decode_tiles_fused_nd(
        ti, lut, side, _two_eb_f32(eb), max_len, int(dec_sym.shape[0]),
        rows_per_tile, sq, ss_max_nd, radius, out_dtype=out_dtype)
    return out.reshape(-1)[:n_out]


def _epilogue_tiles(codes, n_tiles: int, block: int, opos):
    """Zero-pad flat codes to ``n_tiles * block`` and find each tile's
    side-list range."""
    pad = n_tiles * block - codes.shape[0]
    if pad:
        codes = jnp.concatenate([codes, jnp.zeros(pad, codes.dtype)])
    bases = jnp.arange(n_tiles + 1, dtype=jnp.int32) * block
    r = C.outlier_ranges(opos, bases)
    return codes, jnp.stack([r[:-1], r[1:]], axis=1)[:, None, :]


@partial(jax.jit, static_argnames=("max_len", "n_out", "radius", "shape",
                                   "out_dtype"))
def decode_padded_fused(units, dec_sym, dec_len, start_abs, end_abs,
                        total_bits, max_len: int, n_out: int, opos, oval, eb,
                        radius: int, shape=None, out_dtype=jnp.float32):
    """Fused baseline phase 4: padded decode + the standalone epilogue kernel.

    The padded layout + compaction keeps the original decoders' scattered-
    write cost structure (that is the point of the baseline); the epilogue
    (``fused_decode.dequant_reconstruct`` / ``dequant_reconstruct_nd``) then
    fuses dequantization and reconstruction into one chained-scan kernel
    instead of two jnp passes.
    """
    codes, _ = decode_padded_compact(units, dec_sym, dec_len, start_abs,
                                     end_abs, total_bits, max_len, n_out)
    out_dtype = jnp.dtype(out_dtype)
    side = C.side_list(opos, oval)
    sq = fused_squeeze(shape)
    if sq is None:
        tr = _dec.tile_rows(4096)
        block = tr * C.LANES
        n_tiles = -(-n_out // block)
        codes, ranges = _epilogue_tiles(codes, n_tiles, block, opos)
        out = _fus.dequant_reconstruct(
            codes.reshape(n_tiles, tr, C.LANES), ranges, side,
            _two_eb_f32(eb), radius, out_dtype=out_dtype)
        return out.reshape(-1)[:n_out]
    rows_per_tile = fused_tile_rows(sq, 4096)
    block = rows_per_tile * sq[-1]
    n_tiles = -(-n_out // block)
    codes, ranges = _epilogue_tiles(codes, n_tiles, block, opos)
    out = _fus.dequant_reconstruct_nd(
        codes.reshape(n_tiles, rows_per_tile, sq[-1]), ranges, side,
        _two_eb_f32(eb), radius, sq, out_dtype=out_dtype)
    return out.reshape(-1)[:n_out]


@partial(jax.jit, static_argnames=("max_len", "n_out"))
def decode_padded_compact(units, dec_sym, dec_len, start_abs, end_abs,
                          total_bits, max_len: int, n_out: int):
    """Kernel-backed baseline phase 4 (padded layout + ops-level compaction).

    Reproduces the original decoders' scattered-write cost structure."""
    ids, start_local, end_local = _subseq_windows(start_abs, end_abs,
                                                  total_bits)
    n = ids.shape[0]
    ids, start_local, end_local = _lane_block(ids, start_local, end_local,
                                              _dec.DEFAULT_BLOCK_ROWS)
    rows = C.lane_rows(units, ids)
    padded, counts = _dec.decode_padded(rows, start_local, end_local,
                                        C.pack_lut(dec_sym, dec_len), max_len,
                                        int(dec_sym.shape[0]))
    padded, counts = padded[:n], counts.reshape(-1)[:n]
    offsets = hd.output_offsets(counts)
    out_pos = jnp.arange(n_out, dtype=jnp.int32)
    owner = jnp.clip(jnp.searchsorted(offsets, out_pos, side="right") - 1,
                     0, n - 1)
    within = out_pos - offsets[owner]
    return padded[owner, jnp.clip(within, 0, C.MAX_SYMS - 1)], counts


@partial(jax.jit, static_argnames=("n_subseq", "subseqs_per_seq", "max_len",
                                   "early_exit"))
def selfsync_sync(units, dec_sym, dec_len, total_bits, n_subseq: int,
                  subseqs_per_seq: int, max_len: int,
                  early_exit: bool = True):
    """Kernel-backed sync discovery: intra-sequence kernel + inter-sequence
    head chaining (phases 1+2).  Returns (start_abs, counts, rounds)."""
    sps = subseqs_per_seq
    boundaries = jnp.arange(n_subseq, dtype=jnp.int32) * SUBSEQ_BITS
    ids = jnp.arange(n_subseq, dtype=jnp.int32)
    end_local = jnp.clip(
        jnp.minimum(boundaries + SUBSEQ_BITS, total_bits) - boundaries,
        0, C.ROW_UNITS * 32)
    ids, _, end_local = _lane_block(ids, ids, end_local, C.SUBLANES)
    rows = C.lane_rows(units, ids)
    is_head = (jnp.arange(end_local.size, dtype=jnp.int32) % sps) == 0
    n_pad_seq = end_local.size // sps

    run = partial(_sync.selfsync_intra, rows, end_local=end_local,
                  lut=C.pack_lut(dec_sym, dec_len), max_len=max_len,
                  lut_size=int(dec_sym.shape[0]), subseqs_per_seq=sps,
                  early_exit=early_exit)

    def one_pass(heads):
        lane_heads = jnp.where(is_head, jnp.repeat(heads, sps), 0)
        start, counts, landing, rounds = run(
            lane_heads.reshape(end_local.shape))
        # Landing of each sequence's last lane seeds the next sequence.
        last = landing.reshape(n_pad_seq, sps)[:, -1]
        new_heads = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                                     last[:-1] - 128])
        return start, counts, rounds.reshape(n_pad_seq, sps)[:, 0], new_heads

    heads = jnp.zeros((n_pad_seq,), jnp.int32)
    start, counts, rounds, new_heads = one_pass(heads)

    def cond(state):
        heads, new_heads, *_ = state
        return jnp.any(heads != new_heads)

    def body(state):
        _, heads, start, counts, total_rounds = state
        start, counts, rounds, new_heads = one_pass(heads)
        return heads, new_heads, start, counts, total_rounds + rounds

    _, _, start, counts, total_rounds = jax.lax.while_loop(
        cond, body, (heads, new_heads, start, counts, rounds))

    start_abs = boundaries + start.reshape(-1)[:n_subseq]
    return start_abs, counts.reshape(-1)[:n_subseq], total_rounds


# ---------------------------------------------------------------------------
# Encode bit-pack (write-path phase 4)
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("n_units_padded", "subseqs_per_seq",
                                   "min_len"))
def _encode_bitpack_padded(symbols, enc_code, enc_len, n_units_padded: int,
                           subseqs_per_seq: int, min_len: int):
    """Traced body of :func:`encode_bitpack` (sizes fixed for jit)."""
    symbols = symbols.astype(jnp.int32)
    lens = jnp.asarray(enc_len)[symbols].astype(jnp.int32)
    starts = he.prefix_sum(lens) - lens            # exclusive scan [N]
    codes = jnp.asarray(enc_code)[symbols].astype(jnp.uint32)
    total_bits = (starts[-1] + lens[-1]).astype(jnp.int32)
    n = symbols.shape[0]

    # --- output row -> symbol window (mirrors the decode kernels' prep) --
    n_rows = -(-n_units_padded // C.LANES)
    row_base = jnp.arange(n_rows, dtype=jnp.int32) * _enc.ROW_BITS
    # First symbol that can touch each row: the one crossing in from the
    # left (or the first starting in it); its 8-row-aligned symbol row.
    s0 = jnp.maximum(jnp.searchsorted(starts, row_base, side="right") - 1, 0)
    row0 = (s0 // (C.SUBLANES * C.LANES) * C.SUBLANES).astype(jnp.int32)
    sym_rows = C.round_up(-(-n // C.LANES), C.SUBLANES) + _enc.window_rows(
        min_len)
    pad = sym_rows * C.LANES - n

    def rows(a):
        return jnp.concatenate([a, jnp.zeros((pad,), a.dtype)]).reshape(
            sym_rows, C.LANES)

    units = _enc.pack_rows(row0[:, None, None], rows(codes), rows(lens),
                           rows(starts), min_len)
    units = units.reshape(-1)[:n_units_padded]

    gaps, counts, seq_counts = he.stream_metadata(
        starts, total_bits, n_units_padded, subseqs_per_seq)
    return he.EncodedStream(
        units=units, gaps=gaps, counts=counts, seq_counts=seq_counts,
        total_bits=total_bits,
        n_symbols=jnp.asarray(n, jnp.int32),
        subseqs_per_seq=subseqs_per_seq)


def encode_bitpack(symbols, enc_code, enc_len, total_bits: int,
                   subseqs_per_seq: int, min_len: int = 1
                   ) -> he.EncodedStream:
    """Kernel-backed Huffman encode: per-row prefix-sum bit placement.

    ``total_bits`` is the exact payload size (the ``EncoderPlan`` derives
    it from the histogram, so the symbol array never round-trips to host);
    ``min_len`` (the codebook's shortest codeword) bounds the static symbol
    window.  Layout is bit-identical to ``core.huffman.encode.encode``.
    """
    symbols = jnp.asarray(symbols)
    if symbols.shape[0] == 0:
        return he.empty_stream(subseqs_per_seq)
    n_units_padded = he.units_for_bits(total_bits, subseqs_per_seq)
    return _encode_bitpack_padded(symbols, jnp.asarray(enc_code),
                                  jnp.asarray(enc_len), n_units_padded,
                                  subseqs_per_seq, min_len)


# ---------------------------------------------------------------------------
# Histogram + Lorenzo wrappers
# ---------------------------------------------------------------------------

histogram = _hist.histogram


def _lorenzo_rows(flat, n: int):
    """Zero-pad a flat signal to whole ``lorenzo.block_rows`` blocks as
    ``(rows, 128)``."""
    unit = _lor.block_rows(n) * C.LANES
    pad = (-n) % unit or (0 if n else unit)
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros(pad, flat.dtype)])
    return flat.reshape(-1, C.LANES)


@partial(jax.jit, static_argnames=("radius",))
def lorenzo_quantize(x, eb, radius: int = 512):
    """Dual-quant Lorenzo: the 1-D kernel for 1-D inputs.

    N-D inputs take the jnp path (``core.sz.lorenzo.quantize``): the
    multi-axis finite difference is exact integer work around one
    round-to-lattice, and the codes are the same either way.
    """
    if x.ndim != 1:
        from repro.core.sz import lorenzo as _ref

        return _ref.quantize(x, eb, radius=radius)
    n = x.shape[0]
    flat = jnp.asarray(x, jnp.float32)
    xprev = jnp.concatenate([jnp.zeros((1,), jnp.float32), flat[:-1]])
    codes, outlier, resid = _lor.quantize1d(
        _lorenzo_rows(flat, n), _lorenzo_rows(xprev, n), _two_eb_f32(eb),
        radius=radius)
    return (codes.reshape(-1)[:n], outlier.reshape(-1)[:n].astype(bool),
            resid.reshape(-1)[:n])


@partial(jax.jit, static_argnames=("shape",))
def lorenzo_reconstruct(d, eb, shape=None):
    """Inverse Lorenzo; 1-D uses the chained-scan kernel."""
    if shape is None or len(shape) == 1:
        n = d.shape[0]
        out = _lor.reconstruct1d(
            _lorenzo_rows(jnp.asarray(d, jnp.int32), n), _two_eb_f32(eb))
        return out.reshape(-1)[:n]
    q = d.reshape(shape)
    for axis in range(len(shape)):
        q = jnp.cumsum(q, axis=axis)
    return q.astype(jnp.float32) * jnp.float32(2 * eb)
