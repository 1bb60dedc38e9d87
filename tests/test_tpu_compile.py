"""The codec's Pallas kernels compile for a TPU (v5e), checked without one.

Each test lowers one kernel at the block shapes the chip smoke test
(``chip_smoke.py``) runs and compiles it for a described, unattached
``v5e:2x2`` topology with the TPU compiler that ships with libtpu.  A kernel
the compiler refuses (a gather or scatter Mosaic cannot lower, a block not
(8, 128)-aligned, a scalar store to VMEM) fails here, at no chip time.  The
grid is kept short: compile time and the compiler's verdict depend on the
block shapes, not on the number of grid steps.

The topology is described inside a fixture, never at import: only one
process may load libtpu at a time, and every test worker imports this
file.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.huffman.pipeline import ss_max_for_tile
from repro.kernels import common as C
from repro.kernels import fused_decode as F
from repro.kernels import histogram as H
from repro.kernels import huffman_decode as D
from repro.kernels import huffman_encode as E
from repro.kernels import huffman_selfsync as SS
from repro.kernels import lorenzo as L

MAX_LEN = 12                      # codebook.DEFAULT_MAX_LEN
LUT_ROWS = (1 << MAX_LEN) // C.LANES
TILE = 4096                       # pipeline.DEFAULT_TILE_SYMS
RADIUS = 512


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    mp = pytest.MonkeyPatch()
    mp.setenv("TPU_LOG_DIR", "disabled")      # else libtpu logs under /tmp
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:  # no libtpu, or it cannot describe the chip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        mp.undo()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def compiled(one_chip):
    """Lower the kernels for the chip instead of the interpreter, with the
    persistent compilation cache off (an entry compiled for a described
    chip cannot be read back without one).  Both are restored afterwards,
    and jit caches are cleared on the way in and out so no trace crosses
    between interpreter and compiled mode."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    mp = pytest.MonkeyPatch()
    mp.setattr(C, "use_interpreter", lambda: False)
    jax.clear_caches()

    def shape(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def compile_(fn, *args):
        text = jax.jit(fn).lower(*args).compile().as_text()
        assert "tpu_custom_call" in text, "no Mosaic kernel in the program"

    yield shape, compile_
    mp.undo()
    jax.clear_caches()
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _tile_inputs(S, n_tiles: int, ss_max: int, meta_extra: int):
    lanes = C.lane_block(ss_max)
    r = lanes // C.LANES
    return D.TileInputs(
        rows=S((C.ROW_UNITS, n_tiles, r, C.LANES), jnp.uint32),
        start=S((n_tiles, r, C.LANES), jnp.int32),
        end=S((n_tiles, r, C.LANES), jnp.int32),
        lut_base=S((n_tiles, r, C.LANES), jnp.int32),
        meta=S((n_tiles, 1, 2 * lanes + meta_extra), jnp.int32))


def _side(S):
    rows = 2 * C.SUBLANES
    return (S((rows, C.LANES), jnp.int32), S((rows, C.LANES), jnp.int32))


def test_count_subseq(compiled):
    S, compile_ = compiled
    r = 8 * D.DEFAULT_BLOCK_ROWS
    compile_(functools.partial(D.count_subseq, max_len=MAX_LEN,
                               lut_size=1 << MAX_LEN),
             S((C.ROW_UNITS, r, C.LANES), jnp.uint32),
             S((r, C.LANES), jnp.int32), S((r, C.LANES), jnp.int32),
             S((LUT_ROWS, C.LANES), jnp.int32))


def test_decode_tiles(compiled):
    S, compile_ = compiled
    ss_max = ss_max_for_tile(TILE, MAX_LEN)
    compile_(functools.partial(D.decode_tiles, max_len=MAX_LEN,
                               lut_size=1 << MAX_LEN, tile_syms=TILE,
                               ss_max=ss_max),
             _tile_inputs(S, 16, ss_max, 0),
             S((LUT_ROWS, C.LANES), jnp.int32))


def test_decode_tiles_fused_1d(compiled):
    S, compile_ = compiled
    ss_max = ss_max_for_tile(TILE, MAX_LEN)
    compile_(functools.partial(F.decode_tiles_fused, max_len=MAX_LEN,
                               lut_size=1 << MAX_LEN, tile_syms=TILE,
                               ss_max=ss_max, radius=RADIUS),
             _tile_inputs(S, 16, ss_max, 2),
             S((LUT_ROWS, C.LANES), jnp.int32), _side(S),
             S((1,), jnp.float32))


@pytest.mark.parametrize("shape,rows_per_tile", [
    ((512, 512, 512), 8),            # the field phase: Nyx's shape
    ((151936, 1024), 4),             # qwen3-0.6b embedding
    ((28, 1024, 3072), 1),           # stacked MLP up/gate projections
    ((28, 1024, 16, 128), 16),       # stacked attention query weights
])
def test_decode_tiles_fused_nd(compiled, shape, rows_per_tile):
    S, compile_ = compiled
    ss_max = ss_max_for_tile(rows_per_tile * shape[-1], MAX_LEN)
    compile_(functools.partial(F.decode_tiles_fused_nd, max_len=MAX_LEN,
                               lut_size=1 << MAX_LEN,
                               rows_per_tile=rows_per_tile, shape=shape,
                               ss_max=ss_max, radius=RADIUS),
             _tile_inputs(S, 16, ss_max, 2),
             S((LUT_ROWS, C.LANES), jnp.int32), _side(S),
             S((1,), jnp.float32))


def test_decode_tiles_fused_nd_derived(compiled):
    """The nyx field at the geometry ``pipeline.tile_geometry`` derives
    for it: 128 rows of 512 symbols a tile, one register of 1024 lanes."""
    S, compile_ = compiled
    compile_(functools.partial(F.decode_tiles_fused_nd, max_len=MAX_LEN,
                               lut_size=1 << MAX_LEN, rows_per_tile=128,
                               shape=(512, 512, 512), ss_max=1024,
                               radius=RADIUS),
             _tile_inputs(S, 16, 1024, 2),
             S((LUT_ROWS, C.LANES), jnp.int32), _side(S),
             S((1,), jnp.float32))


@pytest.mark.parametrize("shape,rows_per_tile,lanes", [
    ((7, 4, 1024, 576), 16, 768),        # a 1,024-token latent page
    ((20, 4, 32, 128, 128), 64, 640),    # the KDA states, rank 5
    ((20, 4, 12288, 3), 4096, 1024),     # the KDA convolution windows
])
def test_decode_tiles_fused_nd_serving_cache(compiled, shape, rows_per_tile,
                                             lanes):
    """Kimi-Linear-48B-A3B's cache tensors at the geometry
    ``pipeline.tile_geometry`` derives for seeded normal values (rel eb
    1e-3): the benchmark's ``kimi-linear-48b.preempt-resume`` decodes."""
    S, compile_ = compiled
    compile_(functools.partial(F.decode_tiles_fused_nd, max_len=MAX_LEN,
                               lut_size=1 << MAX_LEN,
                               rows_per_tile=rows_per_tile, shape=shape,
                               ss_max=lanes, radius=RADIUS),
             _tile_inputs(S, 16, lanes, 2),
             S((LUT_ROWS, C.LANES), jnp.int32), _side(S),
             S((1,), jnp.float32))


def test_decode_tiles_fused_1d_derived(compiled):
    """The largest flat tile the derivation asks for at 128 symbols a
    window: 131072 symbols over 1024 lanes."""
    S, compile_ = compiled
    compile_(functools.partial(F.decode_tiles_fused, max_len=MAX_LEN,
                               lut_size=1 << MAX_LEN, tile_syms=128 * 1024,
                               ss_max=1024, radius=RADIUS),
             _tile_inputs(S, 16, 1024, 2),
             S((LUT_ROWS, C.LANES), jnp.int32), _side(S),
             S((1,), jnp.float32))


def test_lorenzo_quantize1d(compiled):
    S, compile_ = compiled
    rows = 4 * L.MAX_BLOCK_ROWS
    x = S((rows, C.LANES), jnp.float32)
    compile_(functools.partial(L.quantize1d, radius=RADIUS), x, x,
             S((1,), jnp.float32))


def test_lorenzo_reconstruct1d(compiled):
    S, compile_ = compiled
    rows = 4 * L.MAX_BLOCK_ROWS
    compile_(L.reconstruct1d, S((rows, C.LANES), jnp.int32),
             S((1,), jnp.float32))


def test_histogram(compiled):
    S, compile_ = compiled
    compile_(functools.partial(H.histogram, nbins=2 * RADIUS),
             S((1 << 20,), jnp.uint16))


@pytest.mark.parametrize("min_len,n_syms,n_units", [
    pytest.param(1, 1 << 20, 1 << 16, id="1"),      # 2 bits a code (nyx)
    pytest.param(3, 1 << 20, 5 << 16, id="3"),      # 10 bits a code (danube)
    pytest.param(8, 1 << 18, 3 << 15, id="8"),      # no code under 8 bits
    pytest.param(3, 1000, 384, id="one-partial-step"),
])
def test_pack_rows(compiled, min_len, n_syms, n_units):
    S, compile_ = compiled
    g = E.pack_geometry(n_syms, n_units, min_len)
    sym = (C.round_up(-(-n_syms // C.LANES), C.SUBLANES) + g.window, C.LANES)
    compile_(functools.partial(E.pack_rows, rows=g.rows, window=g.window),
             S((g.steps, 1, 3), jnp.int32), S(sym, jnp.uint32),
             S(sym, jnp.int32), S(sym, jnp.int32))


# Ablation kernels (padded baseline, self-sync, standalone epilogue).


def test_decode_padded(compiled):
    S, compile_ = compiled
    r = 8 * D.DEFAULT_BLOCK_ROWS
    compile_(functools.partial(D.decode_padded, max_len=MAX_LEN,
                               lut_size=1 << MAX_LEN),
             S((C.ROW_UNITS, r, C.LANES), jnp.uint32),
             S((r, C.LANES), jnp.int32), S((r, C.LANES), jnp.int32),
             S((LUT_ROWS, C.LANES), jnp.int32))


def test_selfsync_intra(compiled):
    S, compile_ = compiled
    r = 8 * C.SUBLANES
    compile_(functools.partial(SS.selfsync_intra, max_len=MAX_LEN,
                               lut_size=1 << MAX_LEN, subseqs_per_seq=32),
             S((C.ROW_UNITS, r, C.LANES), jnp.uint32),
             S((r, C.LANES), jnp.int32), S((r, C.LANES), jnp.int32),
             S((LUT_ROWS, C.LANES), jnp.int32))


def test_dequant_reconstruct(compiled):
    S, compile_ = compiled
    rows = D.tile_rows(TILE)
    compile_(functools.partial(F.dequant_reconstruct, radius=RADIUS),
             S((16, rows, C.LANES), jnp.uint16), S((16, 1, 2), jnp.int32),
             _side(S), S((1,), jnp.float32))


def test_dequant_reconstruct_nd(compiled):
    S, compile_ = compiled
    shape = (64, 256, 256)
    compile_(functools.partial(F.dequant_reconstruct_nd, radius=RADIUS,
                               shape=shape),
             S((16, 16, 256), jnp.uint16), S((16, 1, 2), jnp.int32),
             _side(S), S((1,), jnp.float32))
