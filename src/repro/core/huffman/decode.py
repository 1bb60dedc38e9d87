"""Parallel Huffman decoders: self-synchronization and gap-array.

This module contains the *reference* (pure-jnp, jit-able) implementations of
every decoding phase, mirroring the paper's decomposition:

  self-sync (Weissenberger & Schmidt, optimized per paper §IV-A):
    1. intra-sequence synchronization      -> `selfsync_intra`
    2. inter-sequence synchronization      -> `selfsync_inter`
    3. output-index prefix sum             -> `output_offsets`
    4. decode + write                      -> `decode_write` (VMEM-staged
                                              tile variant: `decode_write_tiles`)

  gap-array (Yamamoto et al.):
    1. count decode ("get output idx.")    -> `subseq_scan` with gap starts
    2. prefix sum                          -> `output_offsets`
    3. decode + write                      -> same as above

The Pallas kernels in ``repro.kernels`` implement the same phases with
explicit VMEM tiling; ``repro.kernels.*.ref`` delegates here so every kernel
has a single oracle.  The sequential ``decode_sequential`` is the ground-truth
oracle for everything else.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.core.huffman.bits import SUBSEQ_BITS, peek
from repro.core.huffman.encode import EncodedStream, prefix_sum

# Worst-case codewords per 128-bit subsequence (min codeword length 1).
MAX_SYMS_PER_SUBSEQ = SUBSEQ_BITS
# Decoder lanes per batch of ``decode_write_tiles``.
TILE_BATCH_LANES = 1 << 19


# ---------------------------------------------------------------------------
# Ground-truth sequential decoder
# ---------------------------------------------------------------------------


@partial(jax.jit, static_argnames=("n_symbols", "max_len"))
def decode_sequential(units, dec_sym, dec_len, n_symbols: int, max_len: int):
    """Decode the whole stream with a single sequential scan (oracle)."""

    def step(pos, _):
        win = peek(units, pos, max_len)
        sym = dec_sym[win]
        length = dec_len[win].astype(jnp.int32)
        return pos + length, sym

    _, syms = jax.lax.scan(step, jnp.int32(0), None, length=n_symbols)
    return syms


# ---------------------------------------------------------------------------
# Subsequence window scan (the shared inner loop of every phase)
# ---------------------------------------------------------------------------


@partial(jax.jit, static_argnames=("max_len", "collect"))
def subseq_scan(units, dec_sym, dec_len, start_bits, end_bits, total_bits,
                max_len: int, collect: bool = False, lut_base=None):
    """Decode each subsequence window [start_bits[i], end_bits[i]).

    All arrays are vectorized over subsequences.  Returns
    ``(landing_pos, counts[, symbols])`` where ``landing_pos`` is the absolute
    bit position of the first codeword at-or-after ``end_bits`` (the sync
    point handed to the next subsequence) and ``counts`` is the number of
    codewords whose start lies inside the window (clipped at ``total_bits``).

    ``lut_base`` (optional int32[n]) is a per-subsequence offset added to the
    peeked LUT index -- the batched multi-tensor decoder concatenates the
    decode tables of several codebooks and selects per lane.

    With ``collect=True`` also returns uint16[n, MAX_SYMS_PER_SUBSEQ] padded
    symbols.  The loop is a masked fixed-shape ``while_loop`` -- the TPU
    analogue of the paper's per-warp decode with early exit: iteration stops
    as soon as *every* lane has crossed its window end (`__all_sync`), rather
    than after the worst-case 128 iterations.
    """
    start = start_bits.astype(jnp.int32)
    end = jnp.minimum(end_bits.astype(jnp.int32), total_bits)
    n = start.shape[0]

    syms0 = jnp.zeros((n, MAX_SYMS_PER_SUBSEQ), jnp.uint16) if collect else None

    def cond(state):
        pos, count, syms = state
        return jnp.any(pos < end)

    def body(state):
        pos, count, syms = state
        active = pos < end
        win = peek(units, pos, max_len)
        if lut_base is not None:
            win = win + lut_base
        # Guard: keep the LUT gather in bounds even if a malformed stream
        # or merged-LUT offset produced an out-of-range window index.
        win = jnp.clip(win, 0, dec_sym.shape[0] - 1)
        sym = dec_sym[win]
        length = dec_len[win].astype(jnp.int32)
        if collect:
            # Column write: every active lane stores its count-th symbol.
            idx = jnp.clip(count, 0, MAX_SYMS_PER_SUBSEQ - 1)
            upd = jnp.where(active, sym, syms[jnp.arange(n), idx])
            syms = syms.at[jnp.arange(n), idx].set(upd)
        count = jnp.where(active, count + 1, count)
        # A zero-length LUT entry (unused symbol pattern in zero padding)
        # must still advance to guarantee termination.
        pos = jnp.where(active, pos + jnp.maximum(length, 1), pos)
        return pos, count, syms

    pos0 = jnp.minimum(start, end)
    state = (pos0, jnp.zeros(n, jnp.int32), syms0)
    pos, count, syms = jax.lax.while_loop(cond, body, state)
    if collect:
        return pos, count, syms
    return pos, count


# ---------------------------------------------------------------------------
# Self-synchronization phases
# ---------------------------------------------------------------------------


@partial(jax.jit,
         static_argnames=("n_subseq", "max_len", "subseqs_per_seq", "early_exit"))
def selfsync_intra(units, dec_sym, dec_len, total_bits, n_subseq: int,
                   max_len: int, subseqs_per_seq: int, early_exit: bool = True):
    """Phase 1: per-sequence sync-point discovery.

    Every subsequence starts with a candidate offset 0 at its boundary; each
    round decodes all windows and hands the landing position to the next
    subsequence *within the same sequence*.  ``early_exit=True`` terminates
    when the offsets reach a fixed point (the paper's `__all_sync`
    optimization); ``early_exit=False`` always runs the worst-case
    ``subseqs_per_seq`` rounds (the original W&S behaviour the paper
    improves upon).  Returns (start_bits, rounds_executed).
    """
    boundaries = jnp.arange(n_subseq, dtype=jnp.int32) * SUBSEQ_BITS
    ends = boundaries + SUBSEQ_BITS
    start = boundaries  # offset 0 everywhere

    def round_body(state):
        start, _changed, rounds = state
        landing, _ = subseq_scan(units, dec_sym, dec_len, start, ends,
                                 total_bits, max_len)
        # landing[i] becomes the start of subsequence i+1, except across
        # sequence boundaries (handled by selfsync_inter).
        prop = jnp.roll(landing, 1).at[0].set(start[0])
        is_seq_head = (jnp.arange(n_subseq) % subseqs_per_seq) == 0
        new_start = jnp.where(is_seq_head, start, prop)
        changed = jnp.any(new_start != start)
        return new_start, changed, rounds + 1

    if early_exit:
        def cond(state):
            _start, changed, rounds = state
            return jnp.logical_and(changed, rounds < subseqs_per_seq)
        start, _, rounds = jax.lax.while_loop(
            cond, round_body, (start, jnp.bool_(True), jnp.int32(0)))
    else:
        state = (start, jnp.bool_(True), jnp.int32(0))
        for _ in range(subseqs_per_seq):
            state = round_body(state)
        start, _, rounds = state
    return start, rounds


@partial(jax.jit, static_argnames=("max_len", "subseqs_per_seq", "max_rounds"))
def selfsync_inter(units, dec_sym, dec_len, start_bits, total_bits,
                   max_len: int, subseqs_per_seq: int, max_rounds: int = 8):
    """Phase 2: propagate sync points across sequence boundaries.

    The landing position of each sequence's last subsequence seeds the next
    sequence's first subsequence; sequences whose seed changed re-run their
    intra-sequence propagation.  Thanks to self-synchronization the fixed
    point is reached in one or two rounds on real data; ``max_rounds`` bounds
    the adversarial case (correctness does not depend on it because
    propagation from a *true* start is exact, so round k fixes sequence k at
    the latest -- we chain whole-stream propagation inside each round).
    """
    n_subseq = start_bits.shape[0]
    boundaries = jnp.arange(n_subseq, dtype=jnp.int32) * SUBSEQ_BITS
    ends = boundaries + SUBSEQ_BITS

    def round_body(state):
        start, _changed = state
        landing, _ = subseq_scan(units, dec_sym, dec_len, start, ends,
                                 total_bits, max_len)
        prop = jnp.roll(landing, 1).at[0].set(jnp.int32(0))
        new_start = prop  # every subsequence, including sequence heads
        changed = jnp.any(new_start != start)
        return new_start, changed

    def cond(state):
        _start, changed = state
        return changed

    # Bound total rounds: each round is a full window-parallel propagation;
    # composing `max_rounds * subseqs_per_seq` of them covers the stream.
    def bounded_cond(state_rounds):
        state, rounds = state_rounds
        return jnp.logical_and(cond(state), rounds < max_rounds * subseqs_per_seq)

    def bounded_body(state_rounds):
        state, rounds = state_rounds
        return round_body(state), rounds + 1

    (start, _), rounds = jax.lax.while_loop(
        bounded_cond, bounded_body, ((start_bits, jnp.bool_(True)), jnp.int32(0)))
    return start, rounds


@jax.jit
def output_offsets(counts):
    """Phase 3: exclusive prefix sum of per-subsequence symbol counts."""
    c = counts.astype(jnp.int32)
    return jnp.concatenate([jnp.zeros(1, jnp.int32), prefix_sum(c)])


# ---------------------------------------------------------------------------
# Decode + write
# ---------------------------------------------------------------------------


@partial(jax.jit, static_argnames=("max_len", "n_out"))
def decode_write(units, dec_sym, dec_len, start_bits, total_bits,
                 max_len: int, n_out: int):
    """Phase 4 (baseline layout): padded per-subsequence decode + compaction.

    This reproduces the *original* decoders' write behaviour: each lane
    produces its symbols at strided, data-dependent offsets.  On TPU the
    stride shows up as a full padded (n_subseq, 128) intermediate that is
    then gather-compacted -- ~2x HBM traffic, the structural analogue of the
    uncoalesced global writes the paper fixes.  Kept as the A/B baseline.
    """
    n_subseq = start_bits.shape[0]
    boundaries = jnp.arange(n_subseq, dtype=jnp.int32) * SUBSEQ_BITS
    ends = boundaries + SUBSEQ_BITS
    _, counts, padded = subseq_scan(units, dec_sym, dec_len, start_bits, ends,
                                    total_bits, max_len, collect=True)
    offsets = output_offsets(counts)
    out_pos = jnp.arange(n_out, dtype=jnp.int32)
    owner = jnp.clip(
        jnp.searchsorted(offsets, out_pos, side="right") - 1, 0, n_subseq - 1)
    within = out_pos - offsets[owner]
    return padded[owner, jnp.clip(within, 0, MAX_SYMS_PER_SUBSEQ - 1)], counts


@partial(jax.jit, static_argnames=("max_len", "n_out", "tile_syms", "ss_max"))
def decode_write_tiles(units, dec_sym, dec_len, start_bits, end_bits, offsets,
                       total_bits, max_len: int, n_out: int, tile_syms: int,
                       ss_max: int, lut_base=None):
    """Phase 4 (optimized, paper Alg. 1 analogue): output-tile-centric decode.

    The output is cut into fixed tiles of ``tile_syms`` symbols (the "shared
    memory buffer" -- here a VMEM staging tile).  For each tile we decode the
    (statically bounded) range of subsequences overlapping it and scatter
    *locally* before emitting one dense aligned tile.  ``ss_max`` must be
    >= ``pipeline.ss_max_for_tile(tile_syms, max_len)``.

    ``start_bits``/``end_bits`` are absolute bit windows per subsequence;
    passing them explicitly lets the tuner run this over *gathered* (sorted
    by compression-ratio class) subsequence sets.  ``lut_base`` (optional
    int32[n_subseq]) selects a per-subsequence decode table inside a merged
    LUT (the batched multi-tensor path).

    This jnp version is the oracle for ``repro.kernels.huffman_decode``.
    """
    n_subseq = start_bits.shape[0]
    n_tiles = (n_out + tile_syms - 1) // tile_syms

    tile_base = jnp.arange(n_tiles, dtype=jnp.int32) * tile_syms
    # First subsequence whose output range intersects each tile.
    s0 = jnp.clip(
        jnp.searchsorted(offsets, tile_base, side="right") - 1, 0, n_subseq - 1)

    def decode_tile(t, s0_t):
        subs = jnp.clip(s0_t + jnp.arange(ss_max, dtype=jnp.int32), 0,
                        n_subseq - 1)
        starts = start_bits[subs]
        ends = end_bits[subs]
        lb = None if lut_base is None else lut_base[subs]
        _, counts, padded = subseq_scan(units, dec_sym, dec_len, starts, ends,
                                        total_bits, max_len, collect=True,
                                        lut_base=lb)
        base = tile_base[t]
        local = offsets[subs][:, None] + jnp.arange(MAX_SYMS_PER_SUBSEQ)[None, :] - base
        valid = (
            (jnp.arange(MAX_SYMS_PER_SUBSEQ)[None, :] < counts[:, None])
            & (local >= 0) & (local < tile_syms)
            # guard duplicated (clipped) subsequence rows
            & (subs[:, None] == s0_t + jnp.arange(ss_max, dtype=jnp.int32)[:, None])
        )
        tile = jnp.zeros((tile_syms,), jnp.uint16)
        tile = tile.at[jnp.where(valid, local, tile_syms)].set(
            jnp.where(valid, padded, 0), mode="drop")
        return tile

    # Batches of tiles keep the (tiles, ss_max, MAX_SYMS) intermediates
    # bounded, so a field of 10^8 symbols decodes inside one accelerator's
    # memory.
    tiles = jax.lax.map(lambda a: decode_tile(*a), (jnp.arange(n_tiles), s0),
                        batch_size=max(1, TILE_BATCH_LANES // ss_max))
    return tiles.reshape(-1)[:n_out]


# ---------------------------------------------------------------------------
# Full-pipeline reference decoders
# ---------------------------------------------------------------------------


def gap_starts(stream: EncodedStream):
    n_subseq = stream.gaps.shape[0]
    boundaries = jnp.arange(n_subseq, dtype=jnp.int32) * SUBSEQ_BITS
    return boundaries + stream.gaps.astype(jnp.int32)


def decode_gap_array(stream: EncodedStream, dec_sym, dec_len, max_len: int,
                     n_out: int, tile_syms: int = 4096, use_tiles: bool = True):
    """Gap-array decoder: counts from gap starts, prefix sum, decode+write."""
    starts = gap_starts(stream)
    n_subseq = starts.shape[0]
    boundaries = jnp.arange(n_subseq, dtype=jnp.int32) * SUBSEQ_BITS
    _, counts = subseq_scan(jnp.asarray(stream.units), jnp.asarray(dec_sym),
                            jnp.asarray(dec_len), starts,
                            boundaries + SUBSEQ_BITS, stream.total_bits,
                            max_len)
    offsets = output_offsets(counts)
    if use_tiles:
        from repro.core.huffman.pipeline import ss_max_for_tile

        return decode_write_tiles(stream.units, dec_sym, dec_len, starts,
                                  boundaries + SUBSEQ_BITS, offsets,
                                  stream.total_bits, max_len, n_out,
                                  tile_syms, ss_max_for_tile(tile_syms,
                                                             max_len))
    out, _ = decode_write(stream.units, dec_sym, dec_len, starts,
                          stream.total_bits, max_len, n_out)
    return out


def decode_selfsync(stream: EncodedStream, dec_sym, dec_len, max_len: int,
                    n_out: int, tile_syms: int = 4096, use_tiles: bool = True,
                    early_exit: bool = True):
    """Self-synchronization decoder (no gap array consumed)."""
    units = jnp.asarray(stream.units)
    n_subseq = stream.gaps.shape[0]
    start, _ = selfsync_intra(units, dec_sym, dec_len, stream.total_bits,
                              n_subseq, max_len, stream.subseqs_per_seq,
                              early_exit=early_exit)
    start, _ = selfsync_inter(units, dec_sym, dec_len, start,
                              stream.total_bits, max_len,
                              stream.subseqs_per_seq)
    boundaries = jnp.arange(n_subseq, dtype=jnp.int32) * SUBSEQ_BITS
    _, counts = subseq_scan(units, dec_sym, dec_len, start,
                            boundaries + SUBSEQ_BITS, stream.total_bits,
                            max_len)
    offsets = output_offsets(counts)
    if use_tiles:
        from repro.core.huffman.pipeline import ss_max_for_tile

        return decode_write_tiles(units, dec_sym, dec_len, start,
                                  boundaries + SUBSEQ_BITS, offsets,
                                  stream.total_bits, max_len, n_out,
                                  tile_syms, ss_max_for_tile(tile_syms,
                                                             max_len))
    out, _ = decode_write(units, dec_sym, dec_len, start, stream.total_bits,
                          max_len, n_out)
    return out


@partial(jax.jit, static_argnames=("max_len", "chunk_symbols"))
def decode_chunked(units_rows, chunk_bits, chunk_syms, dec_sym, dec_len,
                   max_len: int, chunk_symbols: int):
    """cuSZ's naive coarse-grained decoder: one sequential scan per chunk."""

    def decode_chunk(units, n_bits):
        def step(pos, _):
            win = peek(units, pos, max_len)
            sym = dec_sym[win]
            length = dec_len[win].astype(jnp.int32)
            valid = pos < n_bits
            return pos + jnp.maximum(length, 1), jnp.where(valid, sym, 0)

        _, syms = jax.lax.scan(step, jnp.int32(0), None, length=chunk_symbols)
        return syms

    return jax.vmap(decode_chunk)(units_rows, chunk_bits.astype(jnp.int32))
