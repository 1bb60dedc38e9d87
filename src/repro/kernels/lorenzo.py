"""Pallas kernels for the 1-D Lorenzo transform (cuSZ dual-quant).

``quantize1d`` is fully parallel (dual-quantization removed the loop-carried
dependence); ``reconstruct1d`` is the inverse prefix sum, a block-local
scan plus a carry kept in VMEM scratch across the sequential grid -- the
standard single-pass chained-scan structure.

Both work on the flat signal laid out as ``(rows, 128)`` and cut into
blocks of ``block_rows`` rows.  2-D/3-D Lorenzo is composed at the ops
level (``repro.kernels.ops.lorenzo_*``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import common as C

#: Rows per block: a multiple of 32, the int8 tile of the outlier mask.
MAX_BLOCK_ROWS = 512


def block_rows(n: int) -> int:
    """Rows per block for an ``n``-element signal."""
    return min(MAX_BLOCK_ROWS, C.round_up(max(-(-n // C.LANES), 1), 32))


def _quant_kernel(teb_ref, x_ref, xprev_ref, o_code_ref, o_out_ref,
                  o_resid_ref, *, radius):
    # two_eb arrives as a runtime input: XLA strength-reduces division by a
    # *constant* to a reciprocal multiply, which flips lattice ties vs the
    # jnp oracle (whose eb is a traced argument -> true division).
    two_eb = teb_ref[0]
    q = jnp.round(x_ref[...] / two_eb).astype(jnp.int32)
    qp = jnp.round(xprev_ref[...] / two_eb).astype(jnp.int32)
    d = q - qp
    code = d + radius
    outlier = (code < 0) | (code >= 2 * radius)
    o_code_ref[...] = jnp.where(outlier, 0, code).astype(jnp.uint16)
    o_out_ref[...] = outlier.astype(jnp.int8)
    o_resid_ref[...] = d


@functools.partial(jax.jit, static_argnames=("radius"))
def quantize1d(x, xprev, two_eb, radius: int = 512):
    """1-D dual-quant Lorenzo over ``(rows, 128)`` float32 ``x``.

    ``xprev`` is ``x`` shifted by one element (0 before the first), so the
    predecessor crosses block boundaries for free; ``two_eb`` is float32
    ``(1,)``.  ``rows`` must be a ``block_rows`` multiple.  Returns (codes
    uint16, outlier int8, residual int32), each ``(rows, 128)``.
    """
    rows = x.shape[0]
    br = block_rows(rows * C.LANES)
    assert rows % br == 0, (rows, br)
    spec = pl.BlockSpec((br, C.LANES), lambda i: (i, 0))
    return pl.pallas_call(
        functools.partial(_quant_kernel, radius=radius),
        name="quantize1d",
        grid=(rows // br,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM), spec, spec],
        out_specs=[spec, spec, spec],
        out_shape=[jax.ShapeDtypeStruct(x.shape, jnp.uint16),
                   jax.ShapeDtypeStruct(x.shape, jnp.int8),
                   jax.ShapeDtypeStruct(x.shape, jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=C.use_interpreter(),
    )(two_eb, x, xprev)


def _recon_kernel(teb_ref, d_ref, o_ref, carry):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        carry[...] = jnp.zeros(carry.shape, jnp.int32)

    q = C.flat_scan(d_ref[...]) + carry[...]
    carry[...] = jnp.broadcast_to(q[-1:, -1:], carry.shape)
    o_ref[...] = q.astype(jnp.float32) * teb_ref[0]


@jax.jit
def reconstruct1d(d, two_eb):
    """Inverse 1-D Lorenzo: chained block scan, ``x = 2*eb * prefix(d)``.

    ``d`` int32 ``(rows, 128)`` (a ``block_rows`` multiple), ``two_eb``
    float32 ``(1,)``; returns float32 ``(rows, 128)``.
    """
    rows = d.shape[0]
    br = block_rows(rows * C.LANES)
    assert rows % br == 0, (rows, br)
    spec = pl.BlockSpec((br, C.LANES), lambda i: (i, 0))
    return pl.pallas_call(
        _recon_kernel,
        name="reconstruct1d",
        grid=(rows // br,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM), spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct(d.shape, jnp.float32),
        scratch_shapes=[pltpu.VMEM((1, C.LANES), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=C.use_interpreter(),
    )(two_eb, d)
