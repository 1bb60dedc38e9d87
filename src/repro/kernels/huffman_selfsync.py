"""Pallas kernel for the self-synchronization phase (W&S, paper §IV-A).

Lanes are subsequences in the ``(rows, 128)`` layout of
``kernels/common.py``; a sequence is ``subseqs_per_seq`` consecutive lanes
of one row, so a block of ``block_rows`` rows holds
``block_rows * 128 / subseqs_per_seq`` sequences.  Each round every lane
decodes its 128-bit window from its current candidate offset and hands the
landing position to the next lane (a one-lane rotation); the first lane of
every sequence keeps its head offset.  The block reaches a fixed point when
no offset changes.

The paper's optimization -- exiting as soon as *all* lanes have validated
their sync point (``__all_sync``) instead of spinning to the worst-case
bound -- maps to the ``while_loop``-with-convergence-predicate here; the
un-optimized variant (``early_exit=False``) runs the worst-case
``subseqs_per_seq`` rounds unconditionally.  Both are kept so the
benchmark can reproduce the paper's ~11% phase-1 win.  Extra rounds past a
sequence's fixed point leave it unchanged, so sharing a block's round count
among its sequences gives the per-sequence answer.

Inter-sequence synchronization (phase 2) chains sequence-head offsets at the
ops level (``repro.kernels.ops.selfsync_sync``) -- a separate launch, as in
the paper.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import common as C


def _selfsync_kernel(rows_ref, head_ref, end_ref, lut_ref, start_ref,
                     counts_ref, land_ref, rounds_ref, *, max_len, lut_size,
                     early_exit, subseqs_per_seq):
    rows = [rows_ref[k] for k in range(C.ROW_UNITS)]
    end = end_ref[...]
    head = head_ref[...]
    lane = jax.lax.broadcasted_iota(jnp.int32, head.shape, 1)
    first = (lane & (subseqs_per_seq - 1)) == 0

    def round_fn(start):
        landing, counts = C.decode_lanes(rows, start, end, lut_ref,
                                         max_len=max_len, lut_size=lut_size)
        # Lane j's landing lies in [128, 128 + max_len) of its row, i.e.
        # offset (landing - 128) into lane j + 1's row.
        prop = jnp.where(first, head, pltpu.roll(landing, 1, 1) - 128)
        return prop, landing, counts

    zero = jnp.zeros(head.shape, jnp.int32)
    if early_exit:
        def cond(state):
            _, _, _, changed, rounds = state
            return jnp.logical_and(changed, rounds < subseqs_per_seq)

        def body(state):
            start, _, _, _, rounds = state
            new_start, landing, counts = round_fn(start)
            return (new_start, landing, counts, jnp.any(new_start != start),
                    rounds + 1)

        start, landing, counts, _, rounds = jax.lax.while_loop(
            cond, body, (head, zero, zero, jnp.bool_(True), jnp.int32(0)))
    else:
        start, landing, counts = jax.lax.fori_loop(
            0, subseqs_per_seq, lambda _, s: round_fn(s[0]),
            (head, zero, zero))
        rounds = jnp.int32(subseqs_per_seq)

    start_ref[...] = start
    counts_ref[...] = counts
    land_ref[...] = landing
    rounds_ref[...] = jnp.full(head.shape, rounds, jnp.int32)


@functools.partial(
    jax.jit,
    static_argnames=("max_len", "lut_size", "subseqs_per_seq", "early_exit",
                     "block_rows"))
def selfsync_intra(rows, heads, end_local, lut, max_len: int, lut_size: int,
                   subseqs_per_seq: int, early_exit: bool = True,
                   block_rows: int = C.SUBLANES):
    """Per-sequence sync discovery.

    rows: uint32 ``(ROW_UNITS, R, 128)``; heads: int32 ``(R, 128)`` holding
    each sequence's candidate head offset in its first lane (other lanes
    ignored); end_local: int32 ``(R, 128)``; ``lut`` the packed decode table.
    ``subseqs_per_seq`` must be a power of two dividing 128 and ``R`` a
    ``block_rows`` multiple.  Returns ``(start_local, counts, landing,
    rounds)``, all int32 ``(R, 128)`` (``rounds`` is per block, repeated in
    every lane).
    """
    if C.LANES % subseqs_per_seq or subseqs_per_seq & (subseqs_per_seq - 1):
        raise ValueError(f"self-sync kernel needs subseqs_per_seq to be a "
                         f"power of two dividing {C.LANES}, got "
                         f"{subseqs_per_seq}")
    n_rows = heads.shape[0]
    assert n_rows % block_rows == 0, (n_rows, block_rows)
    lane_spec = pl.BlockSpec((block_rows, C.LANES), lambda b: (b, 0))
    out = jax.ShapeDtypeStruct(heads.shape, jnp.int32)
    return pl.pallas_call(
        functools.partial(_selfsync_kernel, max_len=max_len,
                          lut_size=lut_size, early_exit=early_exit,
                          subseqs_per_seq=subseqs_per_seq),
        name="selfsync_intra",
        grid=(n_rows // block_rows,),
        in_specs=[pl.BlockSpec((C.ROW_UNITS, block_rows, C.LANES),
                               lambda b: (0, b, 0)),
                  lane_spec, lane_spec,
                  pl.BlockSpec(lut.shape, lambda b: (0, 0))],
        out_specs=[lane_spec] * 4,
        out_shape=[out] * 4,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=C.use_interpreter(),
    )(rows, heads, end_local, lut)
