"""Pallas bit-pack emit kernel: the write-path twin of the decode kernels.

The decode side turned the paper's phases into kernels; this module does
the same for phase 4 of the *encoder*.  Each grid step owns ``rows`` output
rows of 128 uint32 units (4096 bits a row, whole ``(8, 128)`` vector
registers), and places every codeword that touches them in work
proportional to the codewords:

* The per-symbol codeword, length and first-bit position (the exclusive
  ``starts`` scan, computed once on device by the ops wrapper) stay in HBM
  as ``(rows, 128)`` arrays.  A step starts the copies of the three
  windows of symbol rows that can reach its units, then waits on them.
  Its bounds (prefetched into SMEM, one entry a step) name the first
  symbol row and how many to walk: from the row of the codeword that
  crosses into the step to the row of the one that crosses out of it.  The
  loop's trip count is that real span, not the worst-case window.
* A symbol row holds 128 codewords, at most 4096 bits, so it touches at
  most 129 consecutive units: two unit rows, ``r`` and ``r + 1``, where
  ``r`` is the unit row of its first codeword.  Each codeword splits into
  the two words it touches (``hi`` in its unit ``u``, ``lo`` in ``u + 1``)
  with shift arithmetic; words aimed outside the step drop out.
* Placement is one bf16 matmul on the MXU.  The rows of the left operand
  are the four byte planes of ``hi`` and the four of ``lo``; the right one
  is the one-hot ``onehot[k, s] = (u_s - 128 r == k)`` over the 256 units
  of rows ``r`` and ``r + 1``.  The product sums, per unit and byte plane,
  the bytes aimed there; ``lo``'s result moves one unit up.  The four
  planes recombine with shifts, and the two unit rows are added into the
  step's accumulator.

Exactness: codeword bit ranges are disjoint, so the words aimed at one
unit, within one byte plane, occupy disjoint bits.  Their sum is their
bitwise OR, at most 255: bf16 holds 0/1 and 0..255 exactly, f32 sums such
values exactly, and adding the recombined words (int32, disjoint bits)
never carries.  No scatter and no cross-step state.

:func:`pack_geometry` sizes the grid from the stream's own counts (no
knob): about ``STEP_SYMBOL_ROWS`` real symbol rows a step at the stream's
mean code length, at least one vector register of units, and a window that
stays within ``MAX_WINDOW_ROWS``.  Its ``steps`` and ``symbol_rows`` are
what the encode backend counts (``encode_pack_steps``,
``encode_pack_symbol_rows``).

The jnp oracle is ``core.huffman.encode._encode_padded`` (the bit
materialization path); tests assert byte-identical units across backends.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import common as C

ROW_BITS = C.LANES * 32          # bits of one output row of 128 units
STEP_SYMBOL_ROWS = 64            # real symbol rows a grid step aims to walk
MAX_WINDOW_ROWS = 1024           # symbol rows a step may copy (3 x 512 KiB)
ACC_PAD = C.SUBLANES             # accumulator rows before the step's own
PLANES = 16                      # matmul rows: 4 hi + 4 lo byte planes, 0s


@dataclasses.dataclass(frozen=True)
class PackGeometry:
    """Grid of one bit-pack: ``rows`` unit rows a step (a multiple of 8),
    ``window`` symbol rows each step copies, ``steps`` grid steps, and
    ``symbol_rows`` the symbol rows the steps walk, summed (a row shared by
    two steps counts twice)."""

    rows: int
    window: int
    steps: int
    symbol_rows: int


def _window(rows: int, min_len: int, n_symbols: int) -> int:
    """Symbol rows a step of ``rows`` unit rows copies: every codeword
    that can touch it, from an 8-row-aligned start, and never more than
    the stream holds."""
    span = rows * ROW_BITS // max(min_len, 1) + 1
    worst = C.round_up(C.SUBLANES + -(-span // C.LANES), C.SUBLANES)
    return min(worst, C.round_up(-(-n_symbols // C.LANES), C.SUBLANES))


def pack_geometry(n_symbols: int, n_units_padded: int,
                  min_len: int) -> PackGeometry:
    """Rows a step, window and walk of a bit-pack, from the plan's counts.

    A unit row holds ``n_symbols / n_units_padded`` symbol rows on
    average, so a step of ``STEP_SYMBOL_ROWS * n_units_padded / n_symbols``
    unit rows walks about ``STEP_SYMBOL_ROWS`` of them.  That is rounded up
    to whole vector registers, capped at the stream's own rows, and cut by
    registers while the worst-case window (codewords of ``min_len`` bits)
    exceeds ``MAX_WINDOW_ROWS``.
    """
    unit_rows = -(-n_units_padded // C.LANES)
    rows = -(-STEP_SYMBOL_ROWS * n_units_padded // max(n_symbols, 1))
    rows = min(C.round_up(max(rows, 1), C.SUBLANES),
               C.round_up(unit_rows, C.SUBLANES))
    while (rows > C.SUBLANES
           and _window(rows, min_len, n_symbols) > MAX_WINDOW_ROWS):
        rows -= C.SUBLANES
    steps = -(-unit_rows // rows)
    return PackGeometry(rows=rows, window=_window(rows, min_len, n_symbols),
                        steps=steps,
                        symbol_rows=(n_symbols - 1) // C.LANES + steps)


def _split(code, length, p):
    """The words codewords place at first-bit positions ``p`` (relative to
    a unit boundary): ``(u, hi, lo)``, ``hi`` in unit ``u`` (floor, may be
    negative) and ``lo`` in ``u + 1``."""
    u = p >> 5
    o = p & 31
    # Left-align the codeword in the 64-bit window starting at unit u:
    # value64 = code << (64 - o - length); hi lands in unit u, lo in u + 1.
    shift = 64 - o - length                 # in [1, 63] for real symbols
    hi = jnp.where(shift >= 32,
                   code << jnp.clip(shift - 32, 0, 31).astype(jnp.uint32),
                   code >> jnp.clip(32 - shift, 0, 31).astype(jnp.uint32))
    # uint32 << keeps the low 32 bits -- exactly value64 & 0xffffffff.
    lo = jnp.where(shift >= 32, jnp.uint32(0),
                   code << jnp.clip(shift, 0, 31).astype(jnp.uint32))
    return u, hi, lo


def _pack_kernel(bounds_ref, code_hbm, len_hbm, start_hbm, out_ref, code_buf,
                 len_buf, start_buf, acc_ref, sem):
    rows = out_ref.shape[0]
    n_units = rows * C.LANES
    row0 = pl.multiple_of(bounds_ref[0, 0, 0], C.SUBLANES)
    first = bounds_ref[0, 0, 1]
    count = bounds_ref[0, 0, 2]
    w = code_buf.shape[0]
    copies = [pltpu.make_async_copy(src.at[pl.ds(row0, w), :], dst, sem.at[k])
              for k, (src, dst) in enumerate(((code_hbm, code_buf),
                                              (len_hbm, len_buf),
                                              (start_hbm, start_buf)))]
    for cp in copies:
        cp.start()
    acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.int32)
    for cp in copies:
        cp.wait()

    base = pl.program_id(0) * (rows * ROW_BITS)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, C.LANES), 1)
    plane = jax.lax.broadcasted_iota(jnp.int32, (PLANES, C.LANES), 0)
    plane_shift = ((plane & 3) * 8).astype(jnp.uint32)
    unit = jax.lax.broadcasted_iota(jnp.int32, (2 * C.LANES, C.LANES), 0)
    out_plane = jax.lax.broadcasted_iota(jnp.int32, (PLANES, 2 * C.LANES), 0)
    out_shift = (out_plane & 3) * 8

    def symbol_row(j, carry):
        code = code_buf[pl.ds(j, 1), :]
        length = len_buf[pl.ds(j, 1), :]      # 0 => padding symbol
        p = start_buf[pl.ds(j, 1), :] - base  # step-local first bit
        u, hi, lo = _split(code, length, p)
        active = length > 0
        hi = jnp.where(active & (u >= 0) & (u < n_units), hi, jnp.uint32(0))
        lo = jnp.where(active & (u >= -1) & (u < n_units - 1), lo,
                       jnp.uint32(0))
        # Unit row of the row's first codeword (lane 0 is real in every
        # walked row); rows outside [-1, rows) carry no surviving word.
        r = jnp.clip(jnp.sum(jnp.where(lane == 0, p, 0)) >> 12, -1, rows - 1)
        t = u - r * C.LANES
        words = jnp.where(plane < 4, hi, lo)
        bytes_ = jnp.where(plane < 8, (words >> plane_shift) & jnp.uint32(255),
                           jnp.uint32(0))
        a = bytes_.astype(jnp.int32).astype(jnp.float32).astype(jnp.bfloat16)
        onehot = jnp.where(unit == t, 1.0, 0.0).astype(jnp.bfloat16)
        sums = jax.lax.dot_general(a, onehot, (((1,), (1,)), ((), ())),
                                   preferred_element_type=jnp.float32)
        shifted = sums.astype(jnp.int32) << out_shift
        hi_w = jnp.sum(jnp.where(out_plane < 4, shifted, 0), axis=0,
                       keepdims=True)
        lo_w = jnp.sum(jnp.where((out_plane >= 4) & (out_plane < 8),
                                 shifted, 0), axis=0, keepdims=True)
        # lo belongs one unit up; unit 255 of the pair never spills (a
        # symbol row spans at most 129 units from its first one).
        val = hi_w + pltpu.roll(lo_w, 1, 1)
        k = r + ACC_PAD
        acc_ref[pl.ds(k, 1), :] += val[:, :C.LANES]
        acc_ref[pl.ds(k + 1, 1), :] += val[:, C.LANES:]
        return carry

    jax.lax.fori_loop(first, first + count, symbol_row, 0)
    out_ref[...] = jax.lax.bitcast_convert_type(
        acc_ref[ACC_PAD:ACC_PAD + rows, :], jnp.uint32)


@functools.partial(jax.jit, static_argnames=("rows", "window"))
def pack_rows(bounds, codes, lens, starts, rows: int, window: int):
    """Emit packed uint32 units, ``rows`` rows of 128 units a grid step.

    ``codes`` uint32 / ``lens`` / ``starts`` int32 are per-symbol
    ``(sym_rows, 128)`` arrays (length 0 past the last symbol), padded so
    every step's ``window`` symbol rows stay inside.  ``bounds`` int32
    ``(steps, 1, 3)`` holds per step the window's first symbol row (a
    multiple of 8), the first symbol row to walk relative to it, and how
    many rows to walk.  ``rows`` and ``window`` are those of
    :func:`pack_geometry`.  Returns uint32 ``(steps * rows, 128)``.
    """
    steps = bounds.shape[0]
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    return pl.pallas_call(
        _pack_kernel,
        name="pack_rows",
        grid=(steps,),
        in_specs=[pl.BlockSpec((1, 1, 3), lambda i: (i, 0, 0),
                               memory_space=pltpu.SMEM), hbm, hbm, hbm],
        out_specs=pl.BlockSpec((rows, C.LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((steps * rows, C.LANES), jnp.uint32),
        scratch_shapes=[pltpu.VMEM((window, C.LANES), jnp.uint32),
                        pltpu.VMEM((window, C.LANES), jnp.int32),
                        pltpu.VMEM((window, C.LANES), jnp.int32),
                        pltpu.VMEM((rows + 2 * ACC_PAD, C.LANES), jnp.int32),
                        pltpu.SemaphoreType.DMA((3,))],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=C.use_interpreter(),
    )(bounds, codes, lens, starts)
