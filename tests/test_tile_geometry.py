"""Tile geometry of the "tile" decode (``pipeline.tile_geometry``).

Unless ``tile_syms`` pins it, a "tile" decode sizes its tiles from the
plan's host counts so the windows a tile overlaps fill one vector register
of decoder lanes, and provisions exactly the largest span a tile of that
geometry overlaps.  Exactness is the correctness condition (a lane budget
under the true span drops symbols), so the helper is checked against an
independent count of every tile's windows, and decodes at the derived
geometry -- a skewed stream among them -- are checked bit for bit.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import Codec, CodecConfig
from repro.core.huffman import codebook as cb
from repro.core.huffman import encode as he
from repro.core.huffman import pipeline as pp
from repro.data.pipeline import smooth_field

MAX_LEN = 12

# (shape, mean symbols per window): flat, 2-D, 3-D, the danube wk-like
# small-plane 4-D shard, and rows wider than the floor tile.
GEOMETRY_CASES = [
    (None, 3), (None, 26), (None, 65), (None, 120),
    ((300, 700), 13), ((300, 700), 90),
    ((6, 64, 96), 30), ((4, 512, 512), 65),
    ((4, 16, 8, 80), 13), ((2, 40, 6912), 13),
]


def _offsets(rng, n_out: int, mean: int, tail: int = 5) -> np.ndarray:
    """Host offsets of a stream of ``n_out`` symbols at about ``mean``
    symbols per window, with ``tail`` zero-count windows of padding."""
    counts = rng.integers(max(1, mean - mean // 3), mean + mean // 3 + 1,
                          size=n_out // max(1, mean - mean // 3) + 2)
    ends = np.cumsum(counts)
    n_win = int(np.searchsorted(ends, n_out)) + 1
    counts = counts[:n_win].copy()
    counts[-1] -= int(ends[n_win - 1]) - n_out
    counts = np.concatenate([counts, np.zeros(tail, counts.dtype)])
    out = np.zeros(counts.size + 1, np.int64)
    out[1:] = np.cumsum(counts)
    assert out[-1] == n_out
    return out


def _spans_brute(offsets: np.ndarray, tile: int, n_out: int) -> np.ndarray:
    """Lanes each tile needs, counted window by window: from the window
    holding its first symbol to the window holding its last."""
    owner = np.repeat(np.arange(offsets.size - 1), np.diff(offsets))
    owner = owner[:n_out]
    firsts = owner[::tile]
    lasts = owner[np.minimum(np.arange(tile, n_out + tile, tile), n_out) - 1]
    return lasts - firsts + 1


def _n_out(shape):
    return 200_000 if shape is None else int(np.prod(shape))


class TestHelper:
    @pytest.mark.parametrize("shape,mean", GEOMETRY_CASES)
    def test_geometry_properties(self, shape, mean):
        rng = np.random.default_rng(mean + (0 if shape is None else
                                            len(shape)))
        n_out = _n_out(shape)
        offs = _offsets(rng, n_out, mean)
        g = pp.tile_geometry(shape, offs, n_out, MAX_LEN)
        pinned = pp.tile_geometry(shape, offs, n_out, MAX_LEN,
                                  pp.DEFAULT_TILE_SYMS)
        spans = _spans_brute(offs, g.tile, n_out)

        # Exact lane budget: every tile's span fits, whole 128-lane rows.
        assert g.lanes >= spans.max()
        assert g.lanes % 128 == 0 and g.lanes - spans.max() < 128
        # One vector register of lanes, unless the floor tile spans more.
        assert g.lanes <= pp.VREG_LANES or g.tile == pinned.tile
        # Never below the pinned geometry.
        assert g.tile >= pinned.tile
        # Whole rows; beyond 2-D they divide the plane height.
        if shape is not None:
            rows, rem = divmod(g.tile, shape[-1])
            assert rem == 0 and 1 <= rows <= shape[-2]
            if len(shape) >= 3:
                assert shape[-2] % rows == 0
        # The counters' arithmetic.
        assert g.steps == -(-n_out // g.tile) == spans.size
        assert g.windows == spans.sum()

    @pytest.mark.parametrize("shape,mean,least", [
        (None, 65, 0.8), ((300, 700), 13, 0.8),
        # Rows must divide the plane: 128 rows span a few windows past
        # 1024 at this spread, so the tile halves to 64.
        ((4, 512, 512), 65, 0.45)])
    def test_fills_a_vector_register(self, shape, mean, least):
        """At a uniform density the derived tile keeps most of a register
        of lanes busy, and far more than the pinned tile."""
        rng = np.random.default_rng(7)
        n_out = _n_out(shape) * (20 if shape is None else 1)
        offs = _offsets(rng, n_out, mean)
        g = pp.tile_geometry(shape, offs, n_out, MAX_LEN)
        pinned = pp.tile_geometry(shape, offs, n_out, MAX_LEN,
                                  pp.DEFAULT_TILE_SYMS)

        def fill(x):
            return x.windows / (x.steps * pp.VREG_LANES)

        assert fill(g) >= least
        assert fill(g) > 2 * fill(pinned)

    def test_pinned_keeps_fixed_tile_and_bound(self):
        rng = np.random.default_rng(1)
        offs = _offsets(rng, 50_000, 26)
        for tile in (512, 1024, 4096):
            g = pp.tile_geometry(None, offs, 50_000, MAX_LEN, tile)
            assert g.tile == tile
            assert g.lanes == pp.ss_max_for_tile(tile, MAX_LEN)
        g = pp.tile_geometry((6, 64, 96), offs[:36865], 36864, MAX_LEN, 512)
        assert g.tile == pp.fused_tile_rows((6, 64, 96), 512) * 96

    def test_small_tensor_is_one_tile(self):
        rng = np.random.default_rng(2)
        offs = _offsets(rng, 3000, 20)
        g = pp.tile_geometry(None, offs, 3000, MAX_LEN)
        assert g.steps == 1 and g.tile == pp.DEFAULT_TILE_SYMS


def _skewed_stream(rng, n_short=240_000, n_long=6000):
    """1-2-bit codewords with a run of 12-bit ones in the middle: the tile
    over the run spans far more windows than the mean predicts."""
    freq = np.ones(1024, np.int64)
    freq[0], freq[1] = 1 << 22, 1 << 21
    book = cb.build_codebook(freq, max_len=MAX_LEN)
    assert int(book.enc_len[0]) == 1 and int(book.enc_len[1]) == 2
    assert int(book.enc_len[500]) == MAX_LEN
    short = rng.choice(2, size=n_short, p=[2 / 3, 1 / 3])
    long_ = rng.integers(2, 1024, size=n_long)
    syms = np.concatenate([short[:n_short // 2], long_,
                           short[n_short // 2:]]).astype(np.uint16)
    stream = he.encode(syms, book.enc_code, book.enc_len)
    return book, syms, stream


class TestSkewedStream:
    @pytest.fixture(scope="class")
    def skewed(self):
        book, syms, stream = _skewed_stream(np.random.default_rng(3))
        plan = pp.build_plan(stream, book)
        return book, syms, stream, plan

    def test_geometry_steps_down_to_the_exact_span(self, skewed):
        book, syms, _, plan = skewed
        n = len(syms)
        g = pp.tile_geometry(None, plan.host_offsets, n, book.max_len)
        spans = _spans_brute(plan.host_offsets, g.tile, n)
        assert g.lanes >= spans.max() and g.lanes <= pp.VREG_LANES
        # At the tile the mean density asks for, the run would need more
        # than one register of lanes: the geometry stepped down.
        mean = n / np.count_nonzero(np.diff(plan.host_offsets))
        target = int(pp.VREG_LANES * mean) // 1024 * 1024
        assert g.tile < target
        assert _spans_brute(plan.host_offsets, target,
                            n).max() > pp.VREG_LANES

    @pytest.mark.parametrize("backend", ["ref", "pallas"])
    def test_decode_bit_exact(self, skewed, backend):
        book, syms, stream, plan = skewed
        be = pp.get_backend(backend)
        be.reset_stats()
        out = pp.decode(stream, book, len(syms), plan=plan, backend=backend,
                        strategy="tile")
        assert np.array_equal(np.asarray(out), syms)
        g = pp.tile_geometry(None, plan.host_offsets, len(syms),
                             book.max_len)
        assert be.stats["decode_steps"] == g.steps
        assert be.stats["decode_windows"] == g.windows

    @pytest.mark.parametrize("backend", ["ref", "pallas"])
    def test_fused_bit_exact(self, skewed, backend):
        from repro.core.sz import lorenzo

        book, syms, stream, plan = skewed
        n = len(syms)
        opos = jnp.asarray(np.array([3, n // 2, -1, -1], np.int32))
        oval = jnp.asarray(np.array([90, -70, 0, 0], np.int32))
        tr = pp.OutputTransform(eb=1e-3, radius=512, outlier_pos=opos,
                                outlier_val=oval)
        out = pp.decode(stream, book, n, plan=plan, backend=backend,
                        strategy="tile", transform=tr)
        want = lorenzo.dequantize(jnp.asarray(syms), opos, oval, 1e-3, (n,),
                                  radius=512)
        assert np.asarray(out).tobytes() == np.asarray(want).tobytes()


class TestDerivedFusedParity:
    """Fused at the derived geometry == two-pass == ``ref``, bit for bit."""

    @pytest.mark.parametrize("shape", [(90_000,), (120, 700), (8, 64, 96),
                                       (2, 16, 8, 80)])
    def test_fused_matches_two_pass(self, shape):
        x = jnp.asarray(smooth_field(shape, seed=len(shape)))
        cfg = CodecConfig(eb=1e-4, radius=128)
        c = Codec(cfg).compress(x)
        want = np.asarray(Codec(cfg).decompress(c))      # two-pass, ref
        for backend in ("ref", "pallas"):
            fus = Codec(cfg.replace(backend=backend, fused=True))
            fus.backend.reset_stats()
            got = np.asarray(fus.decompress(c))
            assert fus.stats["fused_dispatches"] == 1
            assert fus.stats["fused_fallbacks"] == 0
            assert fus.stats["decode_steps"] >= 1
            assert fus.stats["decode_windows"] >= fus.stats["decode_steps"]
            assert got.tobytes() == want.tobytes(), backend
        two = np.asarray(Codec(cfg.replace(backend="pallas")).decompress(c))
        assert two.tobytes() == want.tobytes()

    def test_config_default_is_derived(self):
        assert CodecConfig().tile_syms is None
        with pytest.raises(ValueError):
            CodecConfig(tile_syms=0)
