"""Pallas bit-pack emit kernel: the write-path twin of the decode kernels.

The decode side turned the paper's phases into kernels; this module does
the same for phase 4 of the *encoder*.  Each grid step owns one output row
of 128 uint32 units (4096 bits):

* The per-symbol codeword, length and first-bit position (the exclusive
  ``starts`` scan, computed once on device by the ops wrapper) stay in HBM
  as ``(rows, 128)`` arrays.  A step copies the window of symbol rows that
  can reach its units: at most ``4096 // min_len + 2`` codewords start
  inside or cross into the row, and the window starts at the 8-row boundary
  below the first of them.
* Each symbol splits its (<= 32-bit, so at most unit-spanning) codeword
  into the two words it touches with shift arithmetic.  Symbols of the
  window that lie outside the row fall outside the unit range and drop out.
* Unit ``k`` is the sum over the window of the contributions aimed at it.
  Codeword bit ranges are disjoint, so the sum IS the bitwise OR -- no
  carries, no scatter, and no cross-step state.

The jnp oracle is ``core.huffman.encode._encode_padded`` (the bit
materialization path); tests assert byte-identical units across backends.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import common as C

ROW_BITS = C.LANES * 32          # output bits per grid step


def window_rows(min_len: int) -> int:
    """Symbol rows a step copies: every codeword that can touch its row,
    from an 8-row-aligned start."""
    sym_max = ROW_BITS // max(min_len, 1) + 2
    return C.round_up(-(-(sym_max + C.SUBLANES * C.LANES - 1) // C.LANES),
                      C.SUBLANES)


def _pack_kernel(row0_ref, code_hbm, len_hbm, start_hbm, out_ref, code_buf,
                 len_buf, start_buf, sem):
    row0 = pl.multiple_of(row0_ref[0, 0, 0], C.SUBLANES)
    n = code_buf.shape[0]
    for src, dst in ((code_hbm, code_buf), (len_hbm, len_buf),
                     (start_hbm, start_buf)):
        cp = pltpu.make_async_copy(src.at[pl.ds(row0, n), :], dst, sem)
        cp.start()
        cp.wait()

    code = code_buf[...]
    length = len_buf[...]                   # 0 => padding symbol
    p = start_buf[...] - pl.program_id(0) * ROW_BITS   # row-local first bit
    # p may be negative (codeword crossing in from the previous row):
    # arithmetic shift / mask give the floor unit and in-unit offset.
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
    active = length > 0
    hi = jax.lax.bitcast_convert_type(
        jnp.where(active, hi, jnp.uint32(0)), jnp.int32)
    lo = jax.lax.bitcast_convert_type(
        jnp.where(active, lo, jnp.uint32(0)), jnp.int32)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, C.LANES), 1)

    def unit(k, row):
        v = jnp.sum(jnp.where(u == k, hi, jnp.where(u == k - 1, lo, 0)))
        return jnp.where(lane == k, v, row)

    row = jax.lax.fori_loop(0, C.LANES, unit,
                            jnp.zeros((1, C.LANES), jnp.int32))
    out_ref[0] = jax.lax.bitcast_convert_type(row, jnp.uint32)


@functools.partial(jax.jit, static_argnames=("min_len"))
def pack_rows(row0, codes, lens, starts, min_len: int):
    """Emit packed uint32 units, one row of 128 units per grid step.

    ``codes`` uint32 / ``lens`` / ``starts`` int32 are per-symbol
    ``(rows, 128)`` arrays (length 0 past the last symbol), padded so every
    window of ``window_rows(min_len)`` rows from ``row0`` stays inside;
    ``row0`` int32 ``(n_rows, 1, 1)`` is each output row's window start (a
    multiple of 8).  Returns uint32 ``(n_rows, 1, 128)``.
    """
    n_rows = row0.shape[0]
    w = window_rows(min_len)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    return pl.pallas_call(
        _pack_kernel,
        name="pack_rows",
        grid=(n_rows,),
        in_specs=[pl.BlockSpec((1, 1, 1), lambda i: (i, 0, 0),
                               memory_space=pltpu.SMEM), hbm, hbm, hbm],
        out_specs=pl.BlockSpec((1, 1, C.LANES), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n_rows, 1, C.LANES), jnp.uint32),
        scratch_shapes=[pltpu.VMEM((w, C.LANES), jnp.uint32),
                        pltpu.VMEM((w, C.LANES), jnp.int32),
                        pltpu.VMEM((w, C.LANES), jnp.int32),
                        pltpu.SemaphoreType.DMA],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=C.use_interpreter(),
    )(row0, codes, lens, starts)
