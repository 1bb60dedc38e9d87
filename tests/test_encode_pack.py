"""The Pallas bit-pack kernel against the bit-materializing oracle.

``kernels/ops._encode_bitpack_padded`` (the ``pallas`` encode backend's
pack, ``huffman_encode.pack_rows`` in the Pallas interpreter here) must emit
the same units and metadata as ``core/huffman/encode._encode_padded`` on
streams built to hit the kernel's edges: words that land exactly on unit
boundaries, codewords crossing unit, row and step boundaries, runs of
one-bit codes longer than a step, a last step that is mostly padding, and
units whose byte planes sum to the largest value the matmul must carry.
"""

from __future__ import annotations

import zlib

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.huffman import encode as he
from repro.core.huffman import pipeline as pp
from repro.kernels import huffman_encode as E
from repro.kernels import ops

SPS = 32


def _stream(case: str):
    """``(symbols, enc_code, enc_len, min_len)`` of one adversarial case;
    the tables need not form a prefix code -- the packer only places
    bits."""
    rng = np.random.default_rng(zlib.crc32(case.encode()))
    if case == "aligned-32-bit":
        # Every codeword fills one unit at offset 0: lo is empty and each
        # unit takes exactly one hi word.
        lens = np.array([32, 32, 32], np.uint8)
        codes = np.array([0xDEADBEEF, 0x80000001, 0xFFFFFFFF], np.uint32)
        syms = rng.integers(0, 3, 2500)
    elif case == "crossing":
        # 13- and 7-bit codes: codewords straddle units, 4096-bit rows and
        # the grid's step boundaries.
        lens = np.array([13, 7, 11, 5], np.uint8)
        codes = (rng.integers(0, 1 << 32, 4, dtype=np.uint64)
                 & ((1 << lens.astype(np.uint64)) - 1)).astype(np.uint32)
        syms = rng.integers(0, 4, 30000)
    elif case == "one-bit-run":
        # A run of 1-bit codes longer than one step (8 rows of 4096 bits)
        # between stretches of 12-bit ones.
        lens = np.array([1, 12, 12], np.uint8)
        codes = np.array([1, 0xABC, 0x5A5], np.uint32)
        syms = np.concatenate([rng.integers(1, 3, 700),
                               np.zeros(40000, np.int64),
                               rng.integers(1, 3, 700)])
    elif case == "padded-last-step":
        # 10-bit codes filling 25 rows exactly: 24 rows a step, so the
        # second step holds one real row and 23 of padding.
        lens = np.array([10, 10], np.uint8)
        codes = np.array([0x3FF, 0x155], np.uint32)
        syms = rng.integers(0, 2, 10240)
    elif case == "full-byte-planes":
        # All-ones codewords of several lengths: every unit is 0xFFFFFFFF,
        # so each of its byte planes sums to 255 over several words.
        lens = np.array([3, 5, 7], np.uint8)
        codes = ((1 << lens.astype(np.uint32)) - 1).astype(np.uint32)
        syms = rng.integers(0, 3, 6000)
    else:
        raise ValueError(case)
    return (syms.astype(np.uint16), codes, lens,
            int(lens[np.unique(syms)].min()))


CASES = ["aligned-32-bit", "crossing", "one-bit-run", "padded-last-step",
         "full-byte-planes"]


def _bounds(syms, lens, g):
    """Per step ``(window row0, first row, last row)`` as the wrapper
    derives them, computed here with numpy."""
    starts = np.concatenate([[0], np.cumsum(lens[syms].astype(np.int64))[:-1]])
    base = np.arange(g.steps + 1) * g.rows * E.ROW_BITS
    s0 = np.maximum(np.searchsorted(starts, base, side="right") - 1, 0)
    srow = s0 // 128
    return srow[:-1] // 8 * 8, srow[:-1], srow[1:], starts


@pytest.mark.parametrize("case", CASES)
def test_pack_rows_matches_oracle(case):
    syms, codes, lens, min_len = _stream(case)
    total = int(lens[syms].astype(np.int64).sum())
    n_units = he.units_for_bits(total, SPS)
    g = E.pack_geometry(len(syms), n_units, min_len)
    row0, first, last, starts = _bounds(syms, lens, g)
    # The case reaches the edge it is named for.
    step_bits = g.rows * E.ROW_BITS
    ends = starts + lens[syms]
    if case == "crossing":
        assert g.steps >= 3
        assert np.any((starts // step_bits) != ((ends - 1) // step_bits))
    if case == "one-bit-run":
        assert min_len == 1 and 40000 > step_bits
    if case == "padded-last-step":
        assert g.steps == 2 and n_units - (g.steps - 1) * g.rows * 128 <= 128
    assert np.all(last - row0 + 1 <= g.window)

    got = ops._encode_bitpack_padded(jnp.asarray(syms), jnp.asarray(codes),
                                     jnp.asarray(lens), n_units, SPS, min_len)
    want = he._encode_padded(jnp.asarray(syms), jnp.asarray(codes),
                             jnp.asarray(lens), n_units, SPS)
    for f in ("units", "gaps", "counts", "seq_counts", "total_bits",
              "n_symbols"):
        assert np.array_equal(np.asarray(getattr(got, f)),
                              np.asarray(getattr(want, f))), (case, f)
    if case == "full-byte-planes":
        assert np.all(np.asarray(got.units)[:total // 32] == 0xFFFFFFFF)


@pytest.mark.parametrize("n,bits,min_len", [
    (1, 1, 1), (1000, 3, 3), (1 << 20, 2, 1), (1 << 20, 10, 3),
    (1 << 18, 12, 8), (12345, 32, 32), (1 << 24, 1, 1)])
def test_pack_geometry(n, bits, min_len):
    n_units = he.units_for_bits(n * bits, SPS)
    g = E.pack_geometry(n, n_units, min_len)
    unit_rows = -(-n_units // 128)
    assert g.rows % 8 == 0 and g.rows >= 8
    assert g.rows <= max(8, -(-unit_rows // 8) * 8)
    assert g.window <= E.MAX_WINDOW_ROWS or g.rows == 8
    assert g.steps == -(-unit_rows // g.rows)
    assert g.symbol_rows == (n - 1) // 128 + g.steps
    # The window holds every codeword that can touch a step: at most
    # rows * 4096 / min_len + 1 of them past the crossing one, from an
    # 8-row-aligned start, or the whole stream.
    span = g.rows * E.ROW_BITS // min_len + 1
    assert (g.window >= 8 + -(-span // 128)
            or g.window >= -(-n // 128))


def test_pack_counters_match_the_walk():
    """``encode_pack_steps`` / ``encode_pack_symbol_rows`` count, on the
    host, exactly the grid steps and the symbol rows the kernel walks."""
    syms, codes, lens, min_len = _stream("crossing")
    freq = np.bincount(syms, minlength=1024)
    be = pp.get_encode_backend("pallas")
    plan = pp.build_encoder_plan(freq, max_len=12, subseqs_per_seq=SPS,
                                 backend=be)
    be.reset_stats()
    stream = pp.encode_with_plan(jnp.asarray(syms), plan, backend=be)
    book = plan.codebook
    g = E.pack_geometry(len(syms), int(stream.units.shape[0]), book.min_len)
    _, first, last, _ = _bounds(syms, book.enc_len.astype(np.int64), g)
    assert be.stats["encode_dispatches"] == 1
    assert be.stats["encode_pack_steps"] == g.steps
    assert be.stats["encode_pack_symbol_rows"] == int(
        (last - first + 1).sum()) == g.symbol_rows
    for name in ("ref", "jnp"):
        other = pp.get_encode_backend(name)
        other.reset_stats()
        pp.encode_with_plan(jnp.asarray(syms), plan, backend=other)
        assert other.stats["encode_pack_steps"] == 0
        assert other.stats["encode_pack_symbol_rows"] == 0
