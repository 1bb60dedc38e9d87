"""Codec sessions: config validation, parity with the free functions,
pytree round trips, cross-consumer plan-cache reuse, shim behavior."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import api
from repro.core.api import Codec, CodecConfig, PlanCache, default_codec
from repro.core.huffman import pipeline as hp
from repro.core.sz import compressor
from repro.data.pipeline import smooth_field


class TestCodecConfig:
    def test_defaults_are_the_paper_setting(self):
        cfg = CodecConfig()
        assert cfg.eb == 1e-3 and cfg.mode == "rel"
        assert cfg.method == "gap" and cfg.backend == "ref"

    @pytest.mark.parametrize("bad", [
        {"mode": "percentile"},
        {"method": "magic"},
        {"strategy": "huge_tiles"},
        {"backend": "cuda"},
        {"t_high": 0},
        {"eb": 0.0},
        {"eb": -1e-3},
        {"radius": 1},
        {"tile_syms": 0},
        {"plan_cache_size": -1},
    ])
    def test_invalid_config_rejected(self, bad):
        with pytest.raises(ValueError):
            CodecConfig(**bad)

    def test_invalid_names_list_valid_options(self):
        with pytest.raises(ValueError, match="tuned"):
            CodecConfig(strategy="nope")
        with pytest.raises(ValueError, match="ref"):
            CodecConfig(backend="nope")

    def test_frozen_and_hashable(self):
        cfg = CodecConfig()
        with pytest.raises(Exception):
            cfg.eb = 2e-3
        assert hash(cfg) == hash(CodecConfig())
        assert cfg.replace(eb=1e-4) != cfg

    def test_config_survives_replace_validation(self):
        with pytest.raises(ValueError):
            CodecConfig().replace(strategy="bogus")


class TestParityWithFreeFunctions:
    """Acceptance: Codec round trip is bit-exact with the engine functions
    over method x backend x strategy."""

    @pytest.mark.parametrize("method", ["gap", "selfsync", "naive_ref"])
    @pytest.mark.parametrize(
        "backend",
        ["ref", pytest.param("pallas", marks=pytest.mark.slow)])
    @pytest.mark.parametrize("strategy", ["tuned", "tile", "padded"])
    def test_bit_exact(self, method, backend, strategy):
        x = smooth_field((48, 300), seed=11)
        codec = Codec(CodecConfig(method=method, backend=backend,
                                  strategy=strategy))
        c = codec.compress(x)
        got = np.asarray(codec.decompress(c))
        want = np.asarray(compressor.decompress(
            c, method=method, backend=backend, strategy=strategy))
        assert got.tobytes() == want.tobytes()
        assert np.abs(got - x).max() <= c.eb_effective

    def test_compress_matches_free_function(self):
        x = smooth_field((64, 64), seed=3)
        a = Codec().compress(x)
        b = api.compress(x)
        assert np.asarray(a.stream.units).tobytes() == \
            np.asarray(b.stream.units).tobytes()
        assert a.eb == b.eb

    def test_decompress_batch_matches_per_tensor(self):
        codec = Codec()
        cs = [codec.compress(smooth_field((30, 40 + 7 * i), seed=i))
              for i in range(3)]
        outs = codec.decompress_batch(cs)
        for c, out in zip(cs, outs):
            ref = np.asarray(codec.decompress(c))
            assert np.asarray(out).tobytes() == ref.tobytes()


class TestTreeRoundTrip:
    @pytest.mark.parametrize(
        "backend",
        ["ref", pytest.param("pallas", marks=pytest.mark.slow)])
    def test_nested_pytree(self, backend):
        tree = {
            "layers": {"w": smooth_field((64, 48), seed=0),
                       "b": smooth_field((256,), seed=1)},
            "stack": [smooth_field((32, 32), seed=2),
                      np.arange(5, dtype=np.int32)],
            "step": 7,
        }
        codec = Codec(CodecConfig(backend=backend))
        ctree = codec.compress_tree(tree)
        assert isinstance(ctree["layers"]["w"], compressor.Compressed)
        assert ctree["stack"][1].dtype == np.int32     # passthrough
        assert ctree["step"] == 7
        back = codec.decompress_tree(ctree)
        for path in (("layers", "w"), ("layers", "b")):
            a = tree[path[0]][path[1]]
            b = np.asarray(back[path[0]][path[1]])
            c = ctree[path[0]][path[1]]
            assert b.shape == a.shape
            assert np.abs(a - b).max() <= c.eb_effective
        assert np.array_equal(np.asarray(back["stack"][1]),
                              tree["stack"][1])

    def test_min_size_floor(self):
        codec = Codec()
        tree = {"big": smooth_field((128, 128), seed=4),
                "tiny": np.ones((4,), np.float32)}
        ctree = codec.compress_tree(tree, min_size=1024)
        assert isinstance(ctree["big"], compressor.Compressed)
        assert isinstance(ctree["tiny"], np.ndarray)

    def test_batched_dispatch_across_tree(self):
        """The whole tree decodes in one class-batched call: dispatch count
        is bounded by CR classes, not leaf count."""
        codec = Codec()
        tree = {f"t{i}": smooth_field((40, 50), seed=i) for i in range(6)}
        ctree = codec.compress_tree(tree)
        codec.reset_stats()
        codec.decompress_tree(ctree)
        assert 0 < codec.stats["decode_write_dispatches"] <= \
            codec.config.t_high + 1


def _fused_case(mode: str, eb: float, seed: int = 21):
    """One compressed 1-D tensor per (mode, eb) cell, with forced outliers
    so the fused outlier scatter is exercised end to end.  radius=128 keeps
    the quantization span wider than the outlier band even for range-
    relative bounds (a rel-eb of 1e-3 caps residuals at ~1/(2 eb) = 500, so
    the default radius 512 can never overflow)."""
    x = np.asarray(smooth_field((6000,), seed=seed)).copy()
    x[[37, 2999, 5511]] += np.float32(40.0) * (x.max() - x.min() + 1.0)
    c = Codec(CodecConfig(eb=eb, mode=mode, radius=128)).compress(x)
    assert int((np.asarray(c.outlier_pos) >= 0).sum()) > 0
    return x, c


class TestFusedCodec:
    """``CodecConfig(fused=True)``: bit-exact with the two-pass path over
    the policy matrix, silent recorded fallback everywhere else."""

    @pytest.mark.parametrize("method", ["gap", "selfsync"])
    @pytest.mark.parametrize(
        "backend",
        ["ref", pytest.param("pallas", marks=pytest.mark.slow)])
    @pytest.mark.parametrize("strategy", ["tile", "padded"])
    @pytest.mark.parametrize("mode,eb", [("rel", 1e-3), ("abs", 2e-3)])
    def test_bit_exact_with_two_pass(self, method, backend, strategy, mode,
                                     eb):
        x, c = _fused_case(mode, eb)
        cfg = CodecConfig(eb=eb, mode=mode, method=method, backend=backend,
                          strategy=strategy)
        two, fus = Codec(cfg), Codec(cfg.replace(fused=True))
        two.backend.reset_stats()
        got = np.asarray(fus.decompress(c))
        assert fus.stats["fused_fallbacks"] == 0
        assert fus.stats["fused_dispatches"] >= 1
        want = np.asarray(two.decompress(c))
        assert got.tobytes() == want.tobytes()
        assert np.abs(got - x).max() <= c.eb_effective

    def test_tuned_strategy_falls_back(self):
        x, c = _fused_case("rel", 1e-3)
        codec = Codec(CodecConfig(strategy="tuned", fused=True))
        codec.backend.reset_stats()
        got = np.asarray(codec.decompress(c))
        assert codec.stats["fused_fallbacks"] == 1
        assert codec.stats["fused_dispatches"] == 0
        want = np.asarray(Codec(CodecConfig(strategy="tuned")).decompress(c))
        assert got.tobytes() == want.tobytes()

    def test_nd_tensor_falls_back(self):
        """Tensors of any rank are fused-eligible; rows wider than
        ``FUSED_MAX_COLS`` still fall back (a tile stages whole rows)."""
        codec = Codec(CodecConfig(fused=True))
        c = codec.compress(smooth_field((2, compressor.FUSED_MAX_COLS + 1),
                                        seed=23))
        codec.backend.reset_stats()
        got = np.asarray(codec.decompress(c))
        assert codec.stats["fused_fallbacks"] == 1
        assert codec.stats["fused_dispatches"] == 0
        want = np.asarray(Codec(CodecConfig()).decompress(c))
        assert got.tobytes() == want.tobytes()

    def test_backend_without_fused_ops_falls_back(self):
        """Acceptance: a backend registered without fused ops serves
        fused=True decodes via two-pass, counting every fallback, with
        bit-exact results."""
        ref = hp.get_backend("ref")
        hp.register_backend("nofused-test", lambda: hp.DecodeBackend(
            name="nofused-test", count_fn=ref.count_fn, sync_fn=ref.sync_fn,
            tiles_fn=ref.tiles_fn, padded_fn=ref.padded_fn))
        try:
            x, c = _fused_case("rel", 1e-3)
            codec = Codec(CodecConfig(backend="nofused-test", fused=True))
            assert not codec.backend.supports_fused
            codec.backend.reset_stats()
            got = np.asarray(codec.decompress(c))
            assert codec.stats["fused_fallbacks"] == 1
            want = np.asarray(Codec(CodecConfig()).decompress(c))
            assert got.tobytes() == want.tobytes()
        finally:
            hp._BACKEND_FACTORIES.pop("nofused-test", None)
            hp._BACKENDS.pop("nofused-test", None)

    def test_batch_mixed_eligibility(self):
        """A fused batch decodes eligible (1-D/2-D) tensors through the
        fused dispatch and the rest through the class-merged two-pass path,
        in order, bit-exact, one recorded fallback per ineligible (here
        wide-row) tensor."""
        codec = Codec(CodecConfig(fused=True))
        wide = compressor.FUSED_MAX_COLS + 1
        cs = [codec.compress(smooth_field((3000,), seed=31)),
              codec.compress(smooth_field((2, wide), seed=32)),
              codec.compress(smooth_field((20, 25), seed=33)),
              codec.compress(smooth_field((3, wide), seed=34))]
        codec.backend.reset_stats()
        outs = codec.decompress_batch(cs)
        assert codec.stats["fused_fallbacks"] == 2
        assert codec.stats["fused_dispatches"] == 2
        refs = Codec(CodecConfig()).decompress_batch(cs)
        for out, ref in zip(outs, refs):
            assert np.asarray(out).tobytes() == np.asarray(ref).tobytes()

    def test_archive_read_uses_fused_path(self, tmp_path):
        """The store reader threads the codec's fused policy into its
        batched decode."""
        from repro.store import Archive, write_archive

        codec = Codec(CodecConfig(fused=True))
        x = smooth_field((5000,), seed=35)
        c = codec.compress(x)
        path = str(tmp_path / "fused.szt")
        write_archive(path, [("x", c, "float32")])
        codec.backend.reset_stats()
        with Archive(path, codec=codec) as ar:
            out = ar.read_all()["x"]
        assert codec.stats["fused_dispatches"] >= 1
        assert codec.stats["fused_fallbacks"] == 0
        want = Codec(CodecConfig()).decompress(c)
        assert np.asarray(out).tobytes() == np.asarray(want).tobytes()

    def test_invalid_fused_rejected(self):
        with pytest.raises(ValueError, match="fused"):
            CodecConfig(fused="yes")


class TestPlanCacheReuse:
    def test_second_decompress_builds_zero_plans(self):
        codec = Codec()
        c = codec.compress(smooth_field((64, 200), seed=5))
        codec.decompress(c)
        codec.backend.reset_stats()
        codec.decompress(c)
        assert codec.stats["plan_builds"] == 0
        assert codec.stats["plan_hits"] >= 1

    def test_checkpoint_second_restore_builds_zero_plans(self, tmp_path):
        """Acceptance: a second restore of the same step through a shared
        Codec is phase-4 only."""
        from repro.checkpoint.manager import CheckpointManager
        codec = Codec(CodecConfig(eb=1e-3))
        mgr = CheckpointManager(str(tmp_path), codec=codec,
                                compress_min_size=256)
        params = {"embed": jnp.asarray(smooth_field((256, 64), seed=6)),
                  "small": jnp.zeros((4,))}
        mgr.save(0, params)
        first = mgr.restore()
        assert first["step"] == 0
        codec.backend.reset_stats()
        second = mgr.restore()
        assert codec.stats["plan_builds"] == 0
        a = np.asarray(first["params"]["embed"])
        b = np.asarray(second["params"]["embed"])
        assert a.tobytes() == b.tobytes()

    def test_direct_decompress_hits_archive_cached_plan(self, tmp_path):
        """Archive reads and direct Codec.decompress share one key space."""
        from repro.store import Archive, write_archive
        codec = Codec()
        x = smooth_field((48, 128), seed=7)
        c = codec.compress(x)
        path = str(tmp_path / "one.szt")
        write_archive(path, [("x", c, "float32")])
        with Archive(path, codec=codec) as ar:
            out = ar.read_all()
            blob = ar.read_chunk("x")
        codec.backend.reset_stats()
        direct = codec.decompress(blob)
        assert codec.stats["plan_builds"] == 0
        assert np.asarray(direct).tobytes() == \
            np.asarray(out["x"]).tobytes()

    def test_isolated_plan_caches_do_not_share(self):
        a = Codec(CodecConfig(), plan_cache=PlanCache())
        b = Codec(CodecConfig(), plan_cache=PlanCache())
        c = a.compress(smooth_field((32, 64), seed=8))
        a.decompress(c)
        b.backend.reset_stats()
        b.decompress(c)
        assert b.stats["plan_builds"] == 1


class TestShims:
    def test_shims_delegate_to_default_codec(self):
        x = smooth_field((32, 96), seed=9)
        c = api.compress(x)
        assert np.asarray(api.decompress(c)).tobytes() == \
            np.asarray(default_codec().decompress(c)).tobytes()

    def test_removed_flags_raise_typeerror(self):
        x = smooth_field((16, 32), seed=10)
        c = api.compress(x)
        for fn, args in ((api.decompress, (c,)),
                         (api.decompress_batch, ([c],)),
                         (api.compress, (x,))):
            with pytest.raises(TypeError, match="CodecConfig"):
                fn(*args, tuned=True)

    def test_unknown_kwarg_still_typeerror(self):
        with pytest.raises(TypeError, match="frobnicate"):
            api.compress(np.zeros((4, 4), np.float32), frobnicate=1)


class TestErrorListings:
    def test_get_backend_lists_available(self):
        with pytest.raises(ValueError) as ei:
            hp.get_backend("not-a-backend")
        for name in hp.available_backends():
            assert name in str(ei.value)

    def test_decode_lists_valid_strategies(self):
        codec = Codec()
        c = codec.compress(smooth_field((16, 64), seed=12))
        with pytest.raises(ValueError) as ei:
            hp.decode(c.stream, c.codebook, c.n_symbols,
                      strategy="diagonal")
        for s in hp.VALID_STRATEGIES:
            assert s in str(ei.value)

    def test_build_plan_lists_valid_methods(self):
        codec = Codec()
        c = codec.compress(smooth_field((16, 64), seed=13))
        with pytest.raises(ValueError) as ei:
            hp.build_plan(c.stream, c.codebook, method="osmosis")
        for m in hp.VALID_PLAN_METHODS:
            assert m in str(ei.value)


class TestEncodeBackendDigests:
    """Content digests must not depend on which backend wrote the bytes,
    nor on how wide the outlier side list happened to be padded."""

    @staticmethod
    def _lattice(n=5000, eb=0.0078125, seed=11):
        k = np.random.default_rng(seed).integers(-400, 400, n).astype(np.int32)
        return (k.astype(np.float32) * np.float32(2 * eb)), eb

    def test_ref_and_jnp_share_digest_and_plans(self):
        from repro.core.cache import compressed_digest

        x, eb = self._lattice()
        host = Codec(CodecConfig(eb=eb, mode="abs", encode_backend="ref"))
        dev = Codec(CodecConfig(eb=eb, mode="abs", encode_backend="jnp"),
                    plan_cache=host.plan_cache)
        ch, cd = host.compress(x), dev.compress(x)
        assert compressed_digest(ch) == compressed_digest(cd)
        host.decompress(ch)             # builds + inserts the plan
        host.backend.reset_stats()
        host.plan_cache.reset_stats()
        dev.decompress(cd)              # must be a cache hit, not a rebuild
        assert dev.stats["plan_builds"] == 0
        assert host.plan_cache.stats["plan_hits"] >= 1

    def test_digest_ignores_outlier_padding(self):
        """Regression: the digest hashes the valid outlier prefix, so a
        writer that pads the side list wider produces the same digest."""
        import dataclasses

        from repro.core.cache import compressed_digest

        x, eb = self._lattice()
        x[::97] += 1000.0               # force some outliers
        c = Codec(CodecConfig(eb=eb, mode="abs")).compress(x)
        n_valid = int((np.asarray(c.outlier_pos) >= 0).sum())
        assert n_valid > 0
        pos = np.asarray(c.outlier_pos)
        val = np.asarray(c.outlier_val)
        wide = dataclasses.replace(
            c,
            outlier_pos=np.concatenate([pos, np.full(64, -1, pos.dtype)]),
            outlier_val=np.concatenate([val, np.zeros(64, val.dtype)]))
        assert compressed_digest(wide) == compressed_digest(c)
