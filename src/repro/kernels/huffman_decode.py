"""Pallas TPU kernels for parallel Huffman decoding (gap-array phases).

Three kernels, all built from ``kernels/common.py``:

  * ``count_subseq`` -- phase 1 ("get output idx."): each lane decodes its
    subsequence window and counts codeword starts.  Grid over blocks of
    ``block_rows * 128`` subsequences.

  * ``decode_tiles`` -- phase 2 (paper Alg. 1): grid over *output* tiles of
    ``tile_syms`` symbols.  Each step decodes the statically bounded set of
    subsequences overlapping its tile, stages their symbols in VMEM and
    emits one dense, aligned tile -- the TPU analogue of the shared-memory
    staged coalesced write.  ``tile_syms`` is the tunable the online tuner
    (core/huffman/pipeline.py) selects per compression-ratio class; the
    per-lane ``lut_base`` input selects a codebook inside a merged decode
    LUT for the batched multi-tensor path.

  * ``decode_padded`` -- the baseline without staging: the padded
    ``(subsequence, MAX_SYMS)`` layout that ops-level compaction gathers,
    the structural analogue of the original decoders' uncoalesced writes.

Per-tile inputs are ``tile_inputs``: unit rows ``(ROW_UNITS, n_tiles, S,
128)``, lane windows and LUT bases ``(n_tiles, S, 128)``, and an SMEM
``meta`` row per tile holding every lane's output offset and symbol count
(plus, for the fused kernels, the tile's outlier range).
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import common as C

DEFAULT_BLOCK_ROWS = C.SUBLANES   # count/padded kernels: 1024 lanes a block


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class TileInputs:
    """Per-tile lane metadata of the tile decode-write kernels."""

    rows: jnp.ndarray       # uint32 (ROW_UNITS, n_tiles, S, 128)
    start: jnp.ndarray      # int32 (n_tiles, S, 128) row-local window start
    end: jnp.ndarray        # int32 (n_tiles, S, 128)
    lut_base: jnp.ndarray   # int32 (n_tiles, S, 128)
    meta: jnp.ndarray       # int32 (n_tiles, 1, W) SMEM: offsets, counts, ...

    @property
    def n_tiles(self) -> int:
        return self.start.shape[0]

    @property
    def n_lanes(self) -> int:
        return self.start.shape[1] * C.LANES


def tile_in_specs(ti: TileInputs, lut) -> list:
    """BlockSpecs of ``(rows, start, end, lut_base, meta, lut)``."""
    s = ti.start.shape[1]
    lane_spec = pl.BlockSpec((1, s, C.LANES), lambda t: (t, 0, 0))
    return [
        pl.BlockSpec((C.ROW_UNITS, 1, s, C.LANES), lambda t: (0, t, 0, 0)),
        lane_spec, lane_spec, lane_spec,
        pl.BlockSpec((1, 1, ti.meta.shape[2]), lambda t: (t, 0, 0),
                     memory_space=pltpu.SMEM),
        pl.BlockSpec(lut.shape, lambda t: (0, 0)),
    ]


def tile_scratch(ti: TileInputs, stage_shape) -> list:
    """Symbol buffer, its transpose and the staging tile."""
    s = ti.start.shape[1]
    return [pltpu.VMEM((C.MAX_SYMS, s, C.LANES), jnp.int32),
            pltpu.VMEM((s, C.LANES, C.MAX_SYMS), jnp.int32),
            pltpu.VMEM(stage_shape, jnp.int32)]


def decode_into_stage(rows_ref, start_ref, end_ref, lutb_ref, meta_ref,
                      lut_ref, y_ref, yt_ref, stage_ref, *, max_len: int,
                      lut_size: int, cols: int, tile: int, ss_max: int):
    """Decode one tile's lanes and stage their symbols (``C.stage_segments``
    layout).  Shared body of the plain and fused tile kernels; lanes past
    ``ss_max`` carry no window."""
    rows = [rows_ref[k, 0] for k in range(C.ROW_UNITS)]
    C.decode_lanes(rows, start_ref[0], end_ref[0], lut_ref, max_len=max_len,
                   lut_size=lut_size, lut_base=lutb_ref[0], y_ref=y_ref)
    C.transpose_symbols(y_ref, yt_ref)
    stage_ref[...] = jnp.zeros(stage_ref.shape, jnp.int32)
    C.stage_segments(yt_ref, meta_ref, stage_ref,
                     n_lanes=start_ref.shape[1] * C.LANES, n_valid=ss_max,
                     cols=cols, tile=tile)


def _block_lanes_specs(block_rows: int):
    return (pl.BlockSpec((C.ROW_UNITS, block_rows, C.LANES),
                         lambda b: (0, b, 0)),
            pl.BlockSpec((block_rows, C.LANES), lambda b: (b, 0)))


def _count_kernel(rows_ref, start_ref, end_ref, lut_ref, counts_ref,
                  land_ref, *, max_len, lut_size):
    rows = [rows_ref[k] for k in range(C.ROW_UNITS)]
    landing, counts = C.decode_lanes(rows, start_ref[...], end_ref[...],
                                     lut_ref, max_len=max_len,
                                     lut_size=lut_size)
    counts_ref[...] = counts
    land_ref[...] = landing


@functools.partial(
    jax.jit,
    static_argnames=("max_len", "lut_size", "block_rows"))
def count_subseq(rows, start_local, end_local, lut, max_len: int,
                 lut_size: int, block_rows: int = DEFAULT_BLOCK_ROWS):
    """Per-subsequence codeword counts + landing positions.

    rows: uint32 ``(ROW_UNITS, N / 128, 128)``; start/end_local: int32
    ``(N / 128, 128)`` row-local bit windows; ``lut`` the packed decode
    table (``C.pack_lut``).  ``N / 128`` must be a ``block_rows`` multiple.
    Returns ``(counts, landing)`` int32 ``(N / 128, 128)``.
    """
    n_rows = start_local.shape[0]
    assert n_rows % block_rows == 0, (n_rows, block_rows)
    rows_spec, lane_spec = _block_lanes_specs(block_rows)
    out = jax.ShapeDtypeStruct(start_local.shape, jnp.int32)
    return pl.pallas_call(
        functools.partial(_count_kernel, max_len=max_len, lut_size=lut_size),
        grid=(n_rows // block_rows,),
        in_specs=[rows_spec, lane_spec, lane_spec,
                  pl.BlockSpec(lut.shape, lambda b: (0, 0))],
        out_specs=[lane_spec, lane_spec],
        out_shape=[out, out],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=C.use_interpreter(),
    )(rows, start_local, end_local, lut)


def _decode_tiles_kernel(rows_ref, start_ref, end_ref, lutb_ref, meta_ref,
                         lut_ref, out_ref, y_ref, yt_ref, stage_ref, *,
                         max_len, lut_size, tile, ss_max):
    decode_into_stage(rows_ref, start_ref, end_ref, lutb_ref, meta_ref,
                      lut_ref, y_ref, yt_ref, stage_ref, max_len=max_len,
                      lut_size=lut_size, cols=C.LANES, tile=tile,
                      ss_max=ss_max)
    out_ref[0] = stage_ref[0].astype(out_ref.dtype)


def tile_rows(tile_syms: int) -> int:
    """Rows of 128 symbols a flat tile is staged in (whole vregs)."""
    return C.round_up(-(-tile_syms // C.LANES), C.SUBLANES)


@functools.partial(
    jax.jit,
    static_argnames=("max_len", "lut_size", "tile_syms", "ss_max"))
def decode_tiles(ti: TileInputs, lut, max_len: int, lut_size: int,
                 tile_syms: int, ss_max: int):
    """Tile-centric decode+write.

    Returns uint16 ``(n_tiles, tile_rows, 128)``: tile ``t`` holds output
    symbols ``[t * tile_syms, (t + 1) * tile_syms)`` flat in its first
    ``tile_syms`` entries (zeros past them).
    """
    tr = tile_rows(tile_syms)
    block = (1, tr, C.LANES)
    return pl.pallas_call(
        functools.partial(_decode_tiles_kernel, max_len=max_len,
                          lut_size=lut_size, tile=tile_syms, ss_max=ss_max),
        grid=(ti.n_tiles,),
        in_specs=tile_in_specs(ti, lut),
        out_specs=pl.BlockSpec(block, lambda t: (t, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((ti.n_tiles, tr, C.LANES), jnp.uint16),
        scratch_shapes=tile_scratch(ti, block),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=C.use_interpreter(),
    )(ti.rows, ti.start, ti.end, ti.lut_base, ti.meta, lut)


def _decode_padded_kernel(rows_ref, start_ref, end_ref, lut_ref, out_ref,
                          counts_ref, y_ref, *, max_len, lut_size):
    rows = [rows_ref[k] for k in range(C.ROW_UNITS)]
    y_ref[...] = jnp.zeros(y_ref.shape, jnp.int32)
    _, counts = C.decode_lanes(rows, start_ref[...], end_ref[...], lut_ref,
                               max_len=max_len, lut_size=lut_size,
                               y_ref=y_ref)
    for s in range(y_ref.shape[1]):
        out_ref[s * C.LANES:(s + 1) * C.LANES, :] = (
            y_ref[:, s, :].T.astype(out_ref.dtype))
    counts_ref[...] = counts


@functools.partial(
    jax.jit,
    static_argnames=("max_len", "lut_size", "block_rows"))
def decode_padded(rows, start_local, end_local, lut, max_len: int,
                  lut_size: int, block_rows: int = DEFAULT_BLOCK_ROWS):
    """Padded baseline: returns (uint16 ``(N, MAX_SYMS)`` symbols, int32
    ``(N / 128, 128)`` counts); inputs as :func:`count_subseq`."""
    n_rows = start_local.shape[0]
    assert n_rows % block_rows == 0, (n_rows, block_rows)
    rows_spec, lane_spec = _block_lanes_specs(block_rows)
    lanes = block_rows * C.LANES
    return pl.pallas_call(
        functools.partial(_decode_padded_kernel, max_len=max_len,
                          lut_size=lut_size),
        grid=(n_rows // block_rows,),
        in_specs=[rows_spec, lane_spec, lane_spec,
                  pl.BlockSpec(lut.shape, lambda b: (0, 0))],
        out_specs=[pl.BlockSpec((lanes, C.MAX_SYMS), lambda b: (b, 0)),
                   lane_spec],
        out_shape=[
            jax.ShapeDtypeStruct((n_rows * C.LANES, C.MAX_SYMS), jnp.uint16),
            jax.ShapeDtypeStruct(start_local.shape, jnp.int32),
        ],
        scratch_shapes=[pltpu.VMEM((C.MAX_SYMS, block_rows, C.LANES),
                                   jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=C.use_interpreter(),
    )(rows, start_local, end_local, lut)
