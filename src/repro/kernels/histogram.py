"""Pallas histogram kernel.

Used twice in the pipeline: (a) quantization-code frequencies for codebook
construction, (b) compression-ratio class counts for the online tuner
(paper Alg. 2 step 2).

A TPU core has no vector scatter-add, so the kernel inverts the loop of a
GPU sub-histogram: the grid runs over ``(rows, 128)`` blocks of the input
in order, and for every bin the block is compared against the bin value
and counted with one vector reduction.  The counts accumulate in an SMEM
output that stays resident across the sequential ("arbitrary") grid.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import common as C

MAX_BLOCK_ROWS = 512


def _hist_kernel(x_ref, out_ref, *, nbins):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        def zero(b, c):
            out_ref[b] = 0
            return c

        jax.lax.fori_loop(0, nbins, zero, 0)

    x = x_ref[...]

    def count(b, c):
        out_ref[b] += jnp.sum((x == b).astype(jnp.int32))
        return c

    jax.lax.fori_loop(0, nbins, count, 0)


@functools.partial(jax.jit, static_argnames=("nbins"))
def histogram(x, nbins: int):
    """int32 histogram of ``x`` (any int dtype, values clipped to
    ``[0, nbins)``)."""
    x = jnp.clip(x.reshape(-1).astype(jnp.int32), 0, nbins - 1)
    n = x.shape[0]
    br = min(MAX_BLOCK_ROWS, C.round_up(max(-(-n // C.LANES), 1),
                                        C.SUBLANES))
    unit = br * C.LANES
    pad = (-n) % unit or (0 if n else unit)
    if pad:                     # -1 matches no bin
        x = jnp.concatenate([x, jnp.full((pad,), -1, jnp.int32)])
    x = x.reshape(-1, C.LANES)
    return pl.pallas_call(
        functools.partial(_hist_kernel, nbins=nbins),
        name="histogram",
        grid=(x.shape[0] // br,),
        in_specs=[pl.BlockSpec((br, C.LANES), lambda i: (i, 0))],
        out_specs=pl.BlockSpec(memory_space=pltpu.SMEM),
        out_shape=jax.ShapeDtypeStruct((nbins,), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=C.use_interpreter(),
    )(x)
