"""Vectorized Huffman encoder (JAX) with subsequence metadata + gap arrays.

Stream format (DESIGN.md §9):
  * MSB-first bit packing into 32-bit *units* (the paper's unit).
  * A *subsequence* is ``SUBSEQ_UNITS = 4`` units = 128 bits -- the work item
    of one decoder lane.
  * A *sequence* is ``subseqs_per_seq`` subsequences -- the work item of one
    decoder grid block.  Codewords cross subsequence and sequence boundaries
    freely (no alignment padding inside the stream; only the tail is padded).

The encoder emits, alongside the packed units:
  * ``gaps``  -- uint8[n_subseq]: bit offset (< max_len) of the first codeword
    *start* at-or-after each subsequence boundary (Yamamoto et al.'s gap
    array).  Self-synchronization decoding ignores this array.
  * ``counts`` -- int32[n_subseq]: number of codewords starting inside each
    subsequence.  This is ground truth used by tests and by the *oracle*
    decode path; the real decoders recompute counts on device (phase 1 /
    the sync phase), exactly as in the paper.

Everything here is jit-able; the host wrapper in ``core/sz/compressor.py``
materializes exact (unpadded) sizes.
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

SUBSEQ_UNITS = 4
UNIT_BITS = 32
SUBSEQ_BITS = SUBSEQ_UNITS * UNIT_BITS  # 128
DEFAULT_SUBSEQS_PER_SEQ = 32            # 4096-bit sequences
UNIT_BATCH = 1 << 18                    # units per batch of encode_gather


def prefix_sum(x, block: int = 1024):
    """Inclusive prefix sum of a 1-D integer array (``jnp.cumsum``'s values).

    Computed as row scans of a ``(rows, block)`` view plus a scan of the
    row totals.  Integer addition is associative, so the result is exact;
    the TPU compiler takes about 14 s for ``jnp.cumsum`` over 2**27 int32
    and about 1.5 s for this form.
    """
    n = x.shape[0]
    rows = jnp.pad(x, (0, (-n) % block)).reshape(-1, block)
    inner = jnp.cumsum(rows, axis=1)
    totals = inner[:, -1]
    return (inner + (jnp.cumsum(totals) - totals)[:, None]).reshape(-1)[:n]


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class EncodedStream:
    """A Huffman-coded bitstream plus decoding metadata (a pytree)."""

    units: jnp.ndarray        # uint32[n_units], padded to a whole sequence
    gaps: jnp.ndarray         # uint8[n_subseq]
    counts: jnp.ndarray       # int32[n_subseq] (ground truth / oracle only)
    seq_counts: jnp.ndarray   # int32[n_seq]    symbols per sequence
    total_bits: jnp.ndarray   # int32[] valid payload bits
    n_symbols: jnp.ndarray    # int32[] total symbols encoded
    subseqs_per_seq: int = dataclasses.field(default=DEFAULT_SUBSEQS_PER_SEQ)

    def tree_flatten(self):
        children = (self.units, self.gaps, self.counts, self.seq_counts,
                    self.total_bits, self.n_symbols)
        return children, self.subseqs_per_seq

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, subseqs_per_seq=aux)

    @property
    def n_subseq(self) -> int:
        return self.gaps.shape[0]

    @property
    def n_seq(self) -> int:
        return self.gaps.shape[0] // self.subseqs_per_seq


def _ceil_to(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def units_for_bits(total_bits: int, subseqs_per_seq: int) -> int:
    """Padded unit count for a ``total_bits`` payload (whole sequences).

    The single audited home of the padding formula: the host encoder, the
    device ``EncoderPlan`` (which sizes the padded stream from a histogram
    instead of the symbol array), and the Pallas bit-pack wrapper all call
    this so every backend emits the same layout.
    """
    n_units = _ceil_to(max(int(total_bits), 1), UNIT_BITS) // UNIT_BITS
    return _ceil_to(n_units, SUBSEQ_UNITS * subseqs_per_seq)


def pack_bits(starts, lens, codes, total_bits, n_bits_padded: int):
    """Materialize the packed uint32 units from per-symbol placement.

    ``starts`` is the exclusive prefix sum of codeword lengths, ``codes``
    the right-aligned codewords; for every output bit a ``searchsorted``
    finds the covering symbol (traced helper, shared by the jit encoder and
    the jnp oracle of the Pallas bit-pack kernel).
    """
    bit_idx = jnp.arange(n_bits_padded, dtype=jnp.int32)
    owner = jnp.searchsorted(starts, bit_idx, side="right") - 1  # [B]
    owner = jnp.clip(owner, 0, starts.shape[0] - 1)
    within = bit_idx - starts[owner]
    code = codes[owner].astype(jnp.uint32)
    length = lens[owner]
    # MSB-first: bit 0 of the codeword is its most significant bit.
    shift = jnp.maximum(length - 1 - within, 0).astype(jnp.uint32)
    bits = (code >> shift) & jnp.uint32(1)
    bits = jnp.where(bit_idx < total_bits, bits, jnp.uint32(0))

    # Pack MSB-first into uint32 units.
    weights = (jnp.uint32(1) << jnp.arange(31, -1, -1, dtype=jnp.uint32))
    return (bits.reshape(-1, UNIT_BITS) * weights[None, :]).sum(
        axis=1, dtype=jnp.uint32
    )


def stream_metadata(starts, total_bits, n_units_padded: int,
                    subseqs_per_seq: int):
    """Gap array + per-subsequence counts from codeword start positions.

    Pure metadata math (no payload access), shared by the jit encoder and
    the Pallas bit-pack wrapper so every encode backend emits bit-identical
    ``gaps`` / ``counts`` / ``seq_counts``.
    """
    n_subseq = n_units_padded // SUBSEQ_UNITS
    boundaries = jnp.arange(n_subseq, dtype=jnp.int32) * SUBSEQ_BITS
    # First codeword start at-or-after each boundary.
    first = jnp.searchsorted(starts, boundaries, side="left")
    first_start = jnp.where(
        first < starts.shape[0], starts[jnp.clip(first, 0, starts.shape[0] - 1)],
        total_bits,
    )
    gaps = jnp.clip(first_start - boundaries, 0, 255).astype(jnp.uint8)
    # Codeword starts inside each subsequence.
    ends = jnp.searchsorted(starts, boundaries + SUBSEQ_BITS, side="left")
    counts = (ends - first).astype(jnp.int32)
    seq_counts = counts.reshape(-1, subseqs_per_seq).sum(
        axis=1, dtype=jnp.int32
    )
    return gaps, counts, seq_counts


@partial(jax.jit, static_argnames=("n_units_padded", "subseqs_per_seq"))
def _encode_padded(
    symbols: jnp.ndarray,
    enc_code: jnp.ndarray,
    enc_len: jnp.ndarray,
    n_units_padded: int,
    subseqs_per_seq: int,
) -> EncodedStream:
    """Core vectorized encoder; ``n_units_padded`` fixed for jit."""
    symbols = symbols.astype(jnp.int32)
    lens = enc_len[symbols].astype(jnp.int32)          # [N]
    starts = prefix_sum(lens) - lens                   # exclusive scan [N]
    total_bits = (starts[-1] + lens[-1]).astype(jnp.int32)

    units = pack_bits(starts, lens, enc_code[symbols], total_bits,
                      n_units_padded * UNIT_BITS)
    gaps, counts, seq_counts = stream_metadata(starts, total_bits,
                                               n_units_padded,
                                               subseqs_per_seq)
    return EncodedStream(
        units=units,
        gaps=gaps,
        counts=counts,
        seq_counts=seq_counts,
        total_bits=total_bits,
        n_symbols=jnp.asarray(symbols.shape[0], jnp.int32),
        subseqs_per_seq=subseqs_per_seq,
    )


@partial(jax.jit, static_argnames=("n_units_padded", "subseqs_per_seq",
                                   "min_len"))
def _encode_gather_padded(
    symbols: jnp.ndarray,
    enc_code: jnp.ndarray,
    enc_len: jnp.ndarray,
    n_units_padded: int,
    subseqs_per_seq: int,
    min_len: int,
) -> EncodedStream:
    """Per-unit gather encoder: the Pallas bit-pack kernel's math in jnp.

    Where :func:`pack_bits` materializes every output *bit* (a
    ``searchsorted`` per bit -- O(total_bits * log n)), this walks output
    *units*: each uint32 unit gathers the <= ``32 // min_len + 2`` codewords
    that can overlap its 32-bit window (one left-crosser plus the starts
    inside it) and ORs together their hi/lo split contributions, the
    arithmetic of ``kernels/huffman_encode.pack_rows``.  Bit-identical to
    ``_encode_padded`` (asserted by the encode parity matrix) at a
    fraction of the work; this is the
    "jnp" encode backend's pack, i.e. the timeable device proxy for the
    kernel.
    """
    sym = symbols.astype(jnp.int32)
    n = sym.shape[0]
    lens = enc_len[sym].astype(jnp.int32)              # [N]
    starts = prefix_sum(lens) - lens                   # exclusive scan [N]
    total_bits = (starts[-1] + lens[-1]).astype(jnp.int32)
    codes = enc_code[sym].astype(jnp.uint32)

    lanes = UNIT_BITS // max(min_len, 1) + 2
    base = jnp.arange(n_units_padded, dtype=jnp.int32) * UNIT_BITS
    # Last codeword starting at-or-before each unit's first bit: the only
    # candidate that can cross in from the left (codewords are contiguous).
    s0 = jnp.clip(jnp.searchsorted(starts, base, side="right") - 1, 0, n - 1)

    def unit(base_u, s0_u):
        k = s0_u + jnp.arange(lanes, dtype=jnp.int32)
        valid = k < n
        kc = jnp.clip(k, 0, n - 1)
        length = jnp.where(valid, lens[kc], 0)
        code = codes[kc]
        # Unit-local placement (p may be negative for the left-crosser);
        # the codeword occupies the 64-bit window
        # ``code << (64 - o - length)`` whose high word lands in unit ``u``
        # and low word in ``u + 1`` -- identical arithmetic to
        # kernels/huffman_encode._split.
        p = starts[kc] - base_u
        u = p >> 5
        o = p & 31
        shift = 64 - o - length
        hi = jnp.where(
            shift >= 32,
            code << jnp.clip(shift - 32, 0, 31).astype(jnp.uint32),
            code >> jnp.clip(32 - shift, 0, 31).astype(jnp.uint32),
        )
        lo = jnp.where(shift >= 32, jnp.uint32(0),
                       code << jnp.clip(shift, 0, 31).astype(jnp.uint32))
        active = length > 0
        contrib = (jnp.where(active & (u == 0), hi, jnp.uint32(0))
                   | jnp.where(active & (u == -1), lo, jnp.uint32(0)))
        return jax.lax.reduce(contrib, jnp.uint32(0), jax.lax.bitwise_or,
                              (0,))

    # Batches of units keep the (units, lanes) intermediates bounded, so a
    # stream of 10^8 symbols encodes inside one accelerator's memory.
    units = jax.lax.map(lambda a: unit(*a), (base, s0),
                        batch_size=UNIT_BATCH)

    gaps, counts, seq_counts = stream_metadata(starts, total_bits,
                                               n_units_padded,
                                               subseqs_per_seq)
    return EncodedStream(
        units=units,
        gaps=gaps,
        counts=counts,
        seq_counts=seq_counts,
        total_bits=total_bits,
        n_symbols=jnp.asarray(n, jnp.int32),
        subseqs_per_seq=subseqs_per_seq,
    )


def encode_gather(
    symbols,
    enc_code,
    enc_len,
    total_bits: int,
    subseqs_per_seq: int = DEFAULT_SUBSEQS_PER_SEQ,
    min_len: int = 1,
) -> EncodedStream:
    """Device-proxy encode: per-unit gather pack under a known bit total.

    ``total_bits`` comes from the ``EncoderPlan`` (histogram dot lengths),
    so the symbol array never has to visit the host for sizing.
    """
    if int(symbols.shape[0]) == 0:
        return empty_stream(subseqs_per_seq)
    n_units_padded = units_for_bits(total_bits, subseqs_per_seq)
    return _encode_gather_padded(jnp.asarray(symbols), jnp.asarray(enc_code),
                                 jnp.asarray(enc_len),
                                 n_units_padded=n_units_padded,
                                 subseqs_per_seq=subseqs_per_seq,
                                 min_len=int(min_len))


def empty_stream(subseqs_per_seq: int = DEFAULT_SUBSEQS_PER_SEQ
                 ) -> EncodedStream:
    """A valid zero-symbol stream (one zero-padded sequence).

    ``_encode_padded`` indexes ``starts[-1]`` and so cannot trace an empty
    symbol array; every encode entry point routes empty inputs here instead.
    """
    n_units_padded = units_for_bits(0, subseqs_per_seq)
    n_subseq = n_units_padded // SUBSEQ_UNITS
    return EncodedStream(
        units=jnp.zeros((n_units_padded,), jnp.uint32),
        gaps=jnp.zeros((n_subseq,), jnp.uint8),
        counts=jnp.zeros((n_subseq,), jnp.int32),
        seq_counts=jnp.zeros((n_subseq // subseqs_per_seq,), jnp.int32),
        total_bits=jnp.asarray(0, jnp.int32),
        n_symbols=jnp.asarray(0, jnp.int32),
        subseqs_per_seq=subseqs_per_seq,
    )


def encode(
    symbols,
    enc_code,
    enc_len,
    subseqs_per_seq: int = DEFAULT_SUBSEQS_PER_SEQ,
) -> EncodedStream:
    """Encode a symbol array.  Host wrapper: sizes the padded stream.

    The padded size is computed from an exact host-side bit count so the
    jit cache keys on (n_units_padded, subseqs_per_seq) only.
    """
    symbols_np = np.asarray(symbols)
    if symbols_np.size == 0:
        return empty_stream(subseqs_per_seq)
    enc_len_np = np.asarray(enc_len)
    total_bits = int(enc_len_np[symbols_np].astype(np.int64).sum())
    n_units_padded = units_for_bits(total_bits, subseqs_per_seq)
    return _encode_padded(
        jnp.asarray(symbols_np),
        jnp.asarray(enc_code),
        jnp.asarray(enc_len),
        n_units_padded=n_units_padded,
        subseqs_per_seq=subseqs_per_seq,
    )


def encode_chunked(
    symbols,
    enc_code,
    enc_len,
    chunk_symbols: int = 16384,
) -> dict:
    """cuSZ-style *coarse-grained* chunked encoding (the paper's baseline).

    Each fixed-size chunk of input symbols is encoded independently and
    padded to a unit boundary; the decoder runs one sequential thread per
    chunk.  The per-chunk padding is the compression-ratio cost the paper
    mentions for small chunks.
    """
    symbols = np.asarray(symbols)
    enc_code = np.asarray(enc_code, dtype=np.uint32)
    enc_len = np.asarray(enc_len, dtype=np.uint8)
    n = symbols.shape[0]
    n_chunks = (n + chunk_symbols - 1) // chunk_symbols

    unit_rows = []
    chunk_bits = np.zeros(n_chunks, dtype=np.int64)
    chunk_syms = np.zeros(n_chunks, dtype=np.int32)
    max_units = 0
    for c in range(n_chunks):
        chunk = symbols[c * chunk_symbols : (c + 1) * chunk_symbols]
        lens = enc_len[chunk].astype(np.int64)
        starts = np.cumsum(lens) - lens
        bits_total = int(lens.sum())
        n_units = max(1, (bits_total + UNIT_BITS - 1) // UNIT_BITS)
        bit_idx = np.arange(n_units * UNIT_BITS, dtype=np.int64)
        owner = np.clip(
            np.searchsorted(starts, bit_idx, side="right") - 1, 0, len(chunk) - 1
        )
        within = bit_idx - starts[owner]
        code = enc_code[chunk[owner]].astype(np.uint64)
        shift = np.maximum(lens[owner] - 1 - within, 0).astype(np.uint64)
        bits = ((code >> shift) & np.uint64(1)).astype(np.uint32)
        bits[bit_idx >= bits_total] = 0
        weights = (1 << np.arange(31, -1, -1, dtype=np.uint64)).astype(np.uint64)
        units = (bits.reshape(-1, UNIT_BITS).astype(np.uint64) * weights).sum(
            axis=1
        ).astype(np.uint32)
        unit_rows.append(units)
        chunk_bits[c] = bits_total
        chunk_syms[c] = len(chunk)
        max_units = max(max_units, n_units)

    padded = np.zeros((n_chunks, max_units), dtype=np.uint32)
    for c, row in enumerate(unit_rows):
        padded[c, : row.shape[0]] = row
    return {
        "units": jnp.asarray(padded),          # [n_chunks, max_units]
        "chunk_bits": jnp.asarray(chunk_bits),
        "chunk_syms": jnp.asarray(chunk_syms),
        "chunk_symbols": chunk_symbols,
        "n_symbols": n,
        # stored bytes: real per-chunk unit counts (unit-aligned padding),
        # matching how cuSZ accounts chunked storage.
        "stored_bytes": int(
            sum(((b + UNIT_BITS - 1) // UNIT_BITS) * 4 for b in chunk_bits)
        ),
    }
