"""Pallas TPU kernels for the fused decode→dequantize→reconstruct path.

The two-pass decompression pipeline materializes the full uint16
quantization-code array in HBM between the Huffman decode-write kernel and
the Lorenzo reconstruction.  The paper's core lesson (§IV) is that the
decoder is memory-bound, so that round trip is pure overhead: these kernels
carry the decoded symbols straight through dequantization (``d = code -
radius`` with the outlier side list written in) and the inverse-Lorenzo
prefix sum (``x = 2·eb · cumsum(d)``) inside the same dispatch, emitting
float output tiles and never writing the code array back to HBM.

Kernel families:

  * ``decode_tiles_fused`` -- the tile decode-write of
    ``huffman_decode.decode_tiles`` plus the 1-D epilogue.  The grid runs
    over output tiles in order ("arbitrary" semantics), so the Lorenzo
    carry (the running prefix sum at each tile boundary) lives in scratch.

  * ``decode_tiles_fused_nd`` -- the same decode stage with the N-D
    epilogue.  Tiles are whole rows along the fastest axis
    (``rows_per_tile`` rows of ``C`` symbols); the 1-D carry generalizes to
    a row carry (the prefix sum over completed rows, reset at each plane
    start, in VMEM scratch) and, for every axis outside the last two, an
    outer carry (the prefix sum over the completed slices along that
    axis).  An outer carry holds whole planes or more, too large for VMEM
    at real sizes, so it lives in an HBM buffer that each tile reads and
    rewrites by DMA.

  * ``dequant_reconstruct`` / ``dequant_reconstruct_nd`` -- the epilogue
    alone, chained after the padded baseline decoder so every decode-write
    strategy has a fused form at every supported ndim.

Every kernel stages a tile in the ``(chunks, rows, 128)`` int32 layout of
``common.stage_segments`` and overwrites its outlier positions with
``common.place_outliers`` (the side list stays in HBM; each tile reads
only its own range of it).

Bit-exactness: the carry-chained prefix sums are int32 integer arithmetic,
identical to the per-axis ``jnp.cumsum`` of ``core.sz.lorenzo.dequantize``;
the float epilogue computes ``q_f32 * two_eb`` in float32 and casts ONCE to
the output dtype -- the op order ``lorenzo.dequantize`` uses -- so fused
output is bit-identical to two-pass output for float32 and for bf16/f16.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import common as C
from repro.kernels import huffman_decode as _dec


def _side_specs() -> list:
    """``two_eb`` (SMEM scalar) and the HBM-resident outlier side list."""
    return [pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY)]


def _side_scratch() -> list:
    return [pltpu.SMEM((C.SUBLANES, C.LANES), jnp.int32),
            pltpu.SMEM((C.SUBLANES, C.LANES), jnp.int32),
            pltpu.SemaphoreType.DMA]


def _residuals(stage_ref, base, meta_ref, lo_at, opos, oval, bufs, sem, *,
               cols: int, n_valid: int, radius: int):
    """Write the tile's outliers into the stage, then turn the stage into
    int32 residuals in place.

    Staging cells past the ``n_valid`` symbols of the tile (the lane padding
    of the last 128-chunk of a row, the row padding of a tile) hold zero
    codes; they are masked to zero residuals so they never enter a carry.
    """
    C.place_outliers(opos, oval, bufs, sem, meta_ref[0, 0, lo_at],
                     meta_ref[0, 0, lo_at + 1], base, stage_ref, cols=cols,
                     bias=radius)
    shape = stage_ref.shape
    ch = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    r = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    c = ch * C.LANES + jax.lax.broadcasted_iota(jnp.int32, shape, 2)
    valid = (c < cols) & (r * cols + c < n_valid)
    stage_ref[...] = jnp.where(valid, stage_ref[...] - radius, 0)


def _recon_flat(d, carry_ref, two_eb, out_dtype):
    """1-D epilogue over one ``(rows, 128)`` tile in row-major order.

    ``carry_ref`` ((1, 128) int32 VMEM) holds the running prefix at the
    tile start in every lane.
    """
    q = C.flat_scan(d) + carry_ref[...]
    carry_ref[...] = jnp.broadcast_to(q[-1:, -1:], carry_ref.shape)
    return (q.astype(jnp.float32) * two_eb).astype(out_dtype)


def _outer_fetch(t, outer, tiles_per_plane: int, levels) -> list:
    """Start loading tile ``t``'s outer carries.

    Level ``(inner, size)`` is the axis whose index advances every
    ``inner`` planes; its HBM buffer keeps one tile-sized block per tile of
    those ``inner`` planes, and the block of tile ``t`` holds the prefix
    over the earlier indices of that axis, as the same tile of the previous
    index stored it.  The first index along the axis starts from zero.
    Returns ``(copy, first, block)`` per level.
    """
    if not levels:
        return []
    plane, j = t // tiles_per_plane, t % tiles_per_plane
    out = []
    for (hbm, buf, sem), (inner, size) in zip(outer, levels):
        block = (plane % inner) * tiles_per_plane + j
        first = (plane // inner) % size == 0
        cp = pltpu.make_async_copy(hbm.at[block], buf, sem)

        @pl.when(jnp.logical_not(first))
        def _():
            cp.start()

        @pl.when(first)
        def _():
            buf[...] = jnp.zeros(buf.shape, jnp.int32)

        out.append((cp, first, block))
    return out


def _recon_rows(d_ref, t, row_carry, outer, fetched, two_eb, out_ref, *,
                tiles_per_plane, rows, cols, out_dtype):
    """N-D epilogue over one staged tile of residuals ``d_ref`` of shape
    ``(chunks, rows_pad, 128)`` (whole rows of the fastest axis, cut into
    128-lane chunks), written to the ``(1, rows, cols)`` block ``out_ref``.

    The inverse Lorenzo is the per-axis cumsum chain: inside the tile
    along the row (lanes, then across chunks) and down the rows; across
    tiles the sequential grid carries

      * ``row_carry`` (chunks, 1, 128) VMEM -- the row prefix over
        completed rows of the current plane, reset at every plane start;
      * ``outer`` -- one ``(hbm, buf, sem)`` per axis outside the last two,
        innermost first (tiles never cross a plane boundary): the prefix
        over completed slices along that axis.  ``fetched`` (from
        :func:`_outer_fetch`) is bringing this tile's blocks into the
        ``buf``s; each level adds its block and keeps the sum, and the
        updated blocks go back by DMA.

    The chunks run in a loop, so the kernel's size does not grow with the
    row width; a last chunk narrower than 128 columns follows it.
    """
    if outer:
        @pl.when(t % tiles_per_plane == 0)
        def _plane_start():
            row_carry[...] = jnp.zeros(row_carry.shape, jnp.int32)

        for cp, first, _ in fetched:
            @pl.when(jnp.logical_not(first))
            def _():
                cp.wait()

    def chunk(k, run):
        e = C.lane_scan(d_ref[k]) + run         # carry across 128-chunks
        f = C.sublane_scan(e) + row_carry[k]
        row_carry[k] = f[-1:, :]
        for _, buf, _ in outer:
            f = f + buf[k]
            buf[k] = f
        x = (f.astype(jnp.float32) * two_eb).astype(out_dtype)
        return jnp.broadcast_to(e[:, -1:], e.shape), x[:rows]

    def body(k, run):
        run, x = chunk(k, run)
        col = pl.multiple_of(k * C.LANES, C.LANES)
        out_ref[0, :, pl.ds(col, C.LANES)] = x
        return run

    full, tail = divmod(cols, C.LANES)
    run = jnp.zeros(d_ref.shape[1:], jnp.int32)
    if full:
        run = jax.lax.fori_loop(0, full, body, run)
    if tail:
        _, x = chunk(full, run)
        out_ref[0, :, full * C.LANES:] = x[:, :tail]
    stores = [pltpu.make_async_copy(buf, hbm.at[block], sem)
              for (hbm, buf, sem), (_, _, block) in zip(outer, fetched)]
    for st in stores:
        st.start()
    for st in stores:
        st.wait()


def _init_carry(t, carry):
    @pl.when(t == 0)
    def _():
        carry[...] = jnp.zeros(carry.shape, jnp.int32)


def _split_refs(refs, n_outer: int):
    """``(out_ref, outer, scratch)`` of a kernel's output and scratch refs.

    A kernel with ``n_outer`` outer carries has one HBM output per carry
    after its tile output, and ends its scratch with one VMEM buffer and
    one DMA semaphore per carry; ``outer`` gathers them as ``(hbm, buf,
    sem)`` triples."""
    out_ref, refs = refs[0], list(refs[1:])
    if not n_outer:
        return out_ref, [], refs
    hbm, scratch = refs[:n_outer], refs[n_outer:]
    bufs, sems = scratch[-2 * n_outer:-n_outer], scratch[-n_outer:]
    return out_ref, list(zip(hbm, bufs, sems)), scratch[:-2 * n_outer]


# ---------------------------------------------------------------------------
# Tile decode + epilogue
# ---------------------------------------------------------------------------


def _fused_tiles_kernel(rows_ref, start_ref, end_ref, lutb_ref, meta_ref,
                        lut_ref, teb_ref, opos, oval, *refs, max_len,
                        lut_size, tile, ss_max, cols, rows, radius,
                        tiles_per_plane, levels, out_dtype):
    out_ref, outer, scratch = _split_refs(refs, len(levels))
    y_ref, yt_ref, stage_ref, pos_buf, val_buf, sem, carry = scratch
    t = pl.program_id(0)
    _init_carry(t, carry)
    fetched = _outer_fetch(t, outer, tiles_per_plane, levels)
    _dec.decode_into_stage(rows_ref, start_ref, end_ref, lutb_ref, meta_ref,
                           lut_ref, y_ref, yt_ref, stage_ref, max_len=max_len,
                           lut_size=lut_size, cols=cols, tile=tile,
                           ss_max=ss_max)
    n_lanes = start_ref.shape[1] * C.LANES
    _residuals(stage_ref, t * tile, meta_ref, 2 * n_lanes, opos, oval,
               (pos_buf, val_buf), sem, cols=cols, n_valid=tile,
               radius=radius)
    if rows is None:                                    # 1-D
        out_ref[0] = _recon_flat(stage_ref[0], carry, teb_ref[0], out_dtype)
    else:
        _recon_rows(stage_ref, t, carry, outer, fetched, teb_ref[0], out_ref,
                    tiles_per_plane=tiles_per_plane, rows=rows, cols=cols,
                    out_dtype=out_dtype)


def _nd_geometry(shape: tuple, rows_per_tile: int):
    """``(cols, chunks, rows_pad, tiles_per_plane, levels)`` of an N-D
    shape.  ``levels`` has one ``(inner, size)`` per axis outside the last
    two, innermost first: the axis advances every ``inner`` planes and
    has ``size`` indices."""
    assert len(shape) >= 2, shape
    plane_rows, cols = shape[-2], shape[-1]
    tiles_per_plane, levels = 0, ()
    if len(shape) >= 3:
        assert plane_rows % rows_per_tile == 0, (shape, rows_per_tile)
        tiles_per_plane = plane_rows // rows_per_tile
        inner = 1
        for size in reversed(shape[:-2]):
            levels += ((inner, size),)
            inner *= size
    chunks = -(-cols // C.LANES)
    rows_pad = C.round_up(rows_per_tile, C.SUBLANES)
    return cols, chunks, rows_pad, tiles_per_plane, levels


def _carry_scratch(chunks, rows_pad, levels) -> list:
    """The row carry, then one VMEM buffer per outer carry, then their DMA
    semaphores."""
    block = pltpu.VMEM((chunks, rows_pad, C.LANES), jnp.int32)
    return ([pltpu.VMEM((chunks, 1, C.LANES), jnp.int32)]
            + [block] * len(levels) + [pltpu.SemaphoreType.DMA] * len(levels))


def _outputs(out_shape, out_block, chunks, rows_pad, tiles_per_plane,
             levels):
    """``(out_specs, out_shapes)``: the output tiles, then one HBM buffer
    per outer carry (a tile-sized block for every tile of ``inner``
    planes)."""
    specs = [pl.BlockSpec(out_block, lambda t: (t, 0, 0))]
    shapes = [out_shape]
    for inner, _ in levels:
        specs.append(pl.BlockSpec(memory_space=pl.ANY))
        shapes.append(jax.ShapeDtypeStruct(
            (inner * tiles_per_plane, chunks, rows_pad, C.LANES), jnp.int32))
    return specs, shapes


def _fused_call(ti, lut, side, two_eb, *, name, kernel, grid_out, out_block,
                stage_shape, carries, outer=(0, 0, 0, ())):
    out_specs, out_shape = _outputs(grid_out, out_block, *outer)
    return pl.pallas_call(
        kernel,
        name=name,
        grid=(ti.n_tiles,),
        in_specs=_dec.tile_in_specs(ti, lut) + _side_specs(),
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=(_dec.tile_scratch(ti, stage_shape) + _side_scratch()
                        + carries),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=C.use_interpreter(),
    )(ti.rows, ti.start, ti.end, ti.lut_base, ti.meta, lut, two_eb,
      *side)[0]


@functools.partial(
    jax.jit,
    static_argnames=("max_len", "lut_size", "tile_syms", "ss_max", "radius",
                     "out_dtype"))
def decode_tiles_fused(ti: _dec.TileInputs, lut, side, two_eb, max_len: int,
                       lut_size: int, tile_syms: int, ss_max: int,
                       radius: int, out_dtype=jnp.float32):
    """Tile decode-write with the fused 1-D dequant/reconstruct epilogue.

    ``ti`` / ``lut`` as ``huffman_decode.decode_tiles`` (whose ``meta`` rows
    end with each tile's outlier range); ``side`` the outlier side list of
    ``common.side_list``; ``two_eb`` float32 ``(1,)``.  Positions past the
    last real symbol decode as zero codes in the final tile only, which no
    later tile reads, so the sliced result is exact.  Returns ``out_dtype``
    ``(n_tiles, tile_rows, 128)`` (tile ``t`` holds symbols ``[t *
    tile_syms, (t + 1) * tile_syms)`` flat, then padding).
    """
    tr = _dec.tile_rows(tile_syms)
    kernel = functools.partial(
        _fused_tiles_kernel, max_len=max_len, lut_size=lut_size,
        tile=tile_syms, ss_max=ss_max, cols=C.LANES, rows=None,
        radius=radius, tiles_per_plane=0, levels=(), out_dtype=out_dtype)
    return _fused_call(
        ti, lut, side, two_eb, name="decode_tiles_fused", kernel=kernel,
        grid_out=jax.ShapeDtypeStruct((ti.n_tiles, tr, C.LANES), out_dtype),
        out_block=(1, tr, C.LANES), stage_shape=(1, tr, C.LANES),
        carries=[pltpu.VMEM((1, C.LANES), jnp.int32)])


@functools.partial(
    jax.jit,
    static_argnames=("max_len", "lut_size", "rows_per_tile", "shape",
                     "ss_max", "radius", "out_dtype"))
def decode_tiles_fused_nd(ti: _dec.TileInputs, lut, side, two_eb,
                          max_len: int, lut_size: int, rows_per_tile: int,
                          shape: tuple, ss_max: int, radius: int,
                          out_dtype=jnp.float32):
    """:func:`decode_tiles_fused` with the N-D inverse-Lorenzo epilogue.

    ``shape`` is the squeezed logical shape, ``(..., R, C)`` with at least
    two axes; each grid step decodes ``rows_per_tile`` whole rows of ``C``
    symbols (``rows_per_tile`` must divide ``R`` beyond 2-D so tiles never
    cross a plane boundary) and reconstructs them against the row carry
    (VMEM) and the outer carries (HBM).  Returns ``out_dtype``
    ``(n_tiles, rows_per_tile, C)``.
    """
    cols, chunks, rows_pad, tpp, levels = _nd_geometry(shape, rows_per_tile)
    kernel = functools.partial(
        _fused_tiles_kernel, max_len=max_len, lut_size=lut_size,
        tile=rows_per_tile * cols, ss_max=ss_max, cols=cols,
        rows=rows_per_tile, radius=radius, tiles_per_plane=tpp,
        levels=levels, out_dtype=out_dtype)
    return _fused_call(
        ti, lut, side, two_eb, name="decode_tiles_fused_nd", kernel=kernel,
        grid_out=jax.ShapeDtypeStruct((ti.n_tiles, rows_per_tile, cols),
                                      out_dtype),
        out_block=(1, rows_per_tile, cols),
        stage_shape=(chunks, rows_pad, C.LANES),
        carries=_carry_scratch(chunks, rows_pad, levels),
        outer=(chunks, rows_pad, tpp, levels))


# ---------------------------------------------------------------------------
# Standalone epilogue (after the padded baseline decoder)
# ---------------------------------------------------------------------------


def _epilogue_kernel(codes_ref, meta_ref, teb_ref, opos, oval, *refs, cols,
                     rows, radius, tiles_per_plane, levels, out_dtype):
    out_ref, outer, scratch = _split_refs(refs, len(levels))
    stage_ref, pos_buf, val_buf, sem, carry = scratch
    t = pl.program_id(0)
    _init_carry(t, carry)
    fetched = _outer_fetch(t, outer, tiles_per_plane, levels)
    stage_ref[...] = jnp.zeros(stage_ref.shape, jnp.int32)
    if rows is None:
        stage_ref[0] = codes_ref[0].astype(jnp.int32)
        tile = stage_ref.shape[1] * C.LANES
    else:
        for k in range(stage_ref.shape[0]):
            w = min(C.LANES, cols - k * C.LANES)
            stage_ref[k, :rows, :w] = codes_ref[
                0, :, k * C.LANES:k * C.LANES + w].astype(jnp.int32)
        tile = rows * cols
    _residuals(stage_ref, t * tile, meta_ref, 0, opos, oval,
               (pos_buf, val_buf), sem, cols=cols, n_valid=tile,
               radius=radius)
    if rows is None:
        out_ref[0] = _recon_flat(stage_ref[0], carry, teb_ref[0], out_dtype)
    else:
        _recon_rows(stage_ref, t, carry, outer, fetched, teb_ref[0], out_ref,
                    tiles_per_plane=tiles_per_plane, rows=rows, cols=cols,
                    out_dtype=out_dtype)


def _epilogue_call(codes, ranges, side, two_eb, *, name, kernel,
                   stage_shape, carries, out_dtype, outer=(0, 0, 0, ())):
    n_tiles, r, c = codes.shape
    out_specs, out_shape = _outputs(
        jax.ShapeDtypeStruct(codes.shape, out_dtype), (1, r, c), *outer)
    return pl.pallas_call(
        kernel,
        name=name,
        grid=(n_tiles,),
        in_specs=[pl.BlockSpec((1, r, c), lambda t: (t, 0, 0)),
                  pl.BlockSpec((1, 1, 2), lambda t: (t, 0, 0),
                               memory_space=pltpu.SMEM)] + _side_specs(),
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=([pltpu.VMEM(stage_shape, jnp.int32)]
                        + _side_scratch() + carries),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=C.use_interpreter(),
    )(codes, ranges, two_eb, *side)[0]


@functools.partial(jax.jit,
                   static_argnames=("radius", "out_dtype"))
def dequant_reconstruct(codes, ranges, side, two_eb, radius: int,
                        out_dtype=jnp.float32):
    """Standalone 1-D epilogue: uint16 codes -> reconstructed floats.

    ``codes`` is ``(n_tiles, rows, 128)`` (flat order, zero-padded at the
    end; pad codes only pollute the final tile's tail); ``ranges`` int32
    ``(n_tiles, 1, 2)`` each tile's side-list range
    (``common.outlier_ranges``).  Returns ``out_dtype`` of ``codes.shape``.
    """
    kernel = functools.partial(_epilogue_kernel, cols=C.LANES, rows=None,
                               radius=radius, tiles_per_plane=0, levels=(),
                               out_dtype=out_dtype)
    return _epilogue_call(codes, ranges, side, two_eb,
                          name="dequant_reconstruct", kernel=kernel,
                          stage_shape=(1,) + codes.shape[1:],
                          carries=[pltpu.VMEM((1, C.LANES), jnp.int32)],
                          out_dtype=out_dtype)


@functools.partial(
    jax.jit, static_argnames=("radius", "shape", "out_dtype"))
def dequant_reconstruct_nd(codes, ranges, side, two_eb, radius: int,
                           shape: tuple, out_dtype=jnp.float32):
    """:func:`dequant_reconstruct` with the N-D epilogue.

    ``codes`` is ``(n_tiles, rows_per_tile, C)`` whole-row tiles of the
    squeezed ``shape`` (zero rows past the end); same carry scheme as
    :func:`decode_tiles_fused_nd`.
    """
    rows_per_tile = codes.shape[1]
    cols, chunks, rows_pad, tpp, levels = _nd_geometry(shape, rows_per_tile)
    kernel = functools.partial(_epilogue_kernel, cols=cols,
                               rows=rows_per_tile, radius=radius,
                               tiles_per_plane=tpp, levels=levels,
                               out_dtype=out_dtype)
    return _epilogue_call(codes, ranges, side, two_eb,
                          name="dequant_reconstruct_nd", kernel=kernel,
                          stage_shape=(chunks, rows_pad, C.LANES),
                          carries=_carry_scratch(chunks, rows_pad, levels),
                          out_dtype=out_dtype,
                          outer=(chunks, rows_pad, tpp, levels))
