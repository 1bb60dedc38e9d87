"""N-D / low-precision fused decode: the property-based parity suite.

The fused decode→dequantize→inverse-Lorenzo path now covers the full
ndim {1,2,3} x dtype {f32, bf16, f16} lattice.  Correctness across that
lattice -- on both backends, both fused-capable strategies, and both error
bound modes, with outliers forced past the quantization radius -- is the
whole risk, so this module asserts, cell by cell:

    fused  ==  two-pass (same backend)  ==  two-pass ("ref" backend)

bit-for-bit, with ``stats["fused_dispatches"]`` counted and
``stats["fused_fallbacks"]`` zero for every supported cell.  A seeded
deterministic sweep always runs; when ``hypothesis`` is installed the same
invariant is additionally driven over randomized shapes (the
``tests/test_faults.py`` pattern).  Checked-in golden vectors
(``tests/golden/fused_nd_golden.json``) pin the compressed bytes and the
reconstruction checksums across versions, hypothesis or not.
"""

import hashlib
import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.api import Codec, CodecConfig
from repro.core.huffman import pipeline as hp
from repro.core.sz import compressor as sz
from repro.data.pipeline import smooth_field

try:
    from hypothesis import example, given, settings
    from hypothesis import strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:                       # container has no hypothesis
    HAVE_HYPOTHESIS = False

GOLDEN_PATH = os.path.join(os.path.dirname(__file__), "golden",
                           "fused_nd_golden.json")

SHAPES = {1: (6000,), 2: (56, 72), 3: (6, 24, 40)}
DTYPES = {"f32": jnp.float32, "bf16": jnp.bfloat16, "f16": jnp.float16}
RADIUS = 128      # small radius so the forced spikes overflow it
TILE_SYMS = 512   # small tiles so every decode crosses many carry chains


def _field(shape, dtype, seed):
    """Lorenzo-friendly field with spikes guaranteed past the radius.

    The spikes make ``|residual| >= radius`` at known positions, so the
    outlier side list -- and the fused kernels' outlier scatter -- is
    exercised in every cell (asserted in ``_make_case``).
    """
    x = np.asarray(smooth_field(shape, seed=seed)).copy()
    flat = x.reshape(-1)
    rng = np.random.default_rng(seed + 1000)
    idx = rng.choice(flat.size, size=min(flat.size, max(4, flat.size // 400)),
                     replace=False)
    flat[idx] += np.float32(40.0) * (x.max() - x.min() + 1.0) * \
        rng.choice(np.asarray([-1.0, 1.0], np.float32), size=idx.size)
    return jnp.asarray(x).astype(dtype)


_CASES: dict = {}


def _make_case(ndim, dtype_key, mode, eb):
    """One compressed tensor + its two-pass ref baseline per lattice cell
    (memoized: compression is the expensive part of every cell)."""
    key = (ndim, dtype_key, mode, eb)
    if key not in _CASES:
        x = _field(SHAPES[ndim], DTYPES[dtype_key], seed=7 * ndim + 13)
        codec = Codec(CodecConfig(eb=eb, mode=mode, radius=RADIUS,
                                  tile_syms=TILE_SYMS))
        c = codec.compress(x)
        assert int((np.asarray(c.outlier_pos) >= 0).sum()) > 0, \
            "case must exercise the outlier scatter"
        want = np.asarray(codec.decompress(c))   # two-pass on "ref"
        _CASES[key] = (x, c, want)
    return _CASES[key]


class TestFusedNdParity:
    """fused == two-pass == ref over the full eligibility lattice."""

    @pytest.mark.parametrize("mode,eb", [("rel", 1e-4), ("abs", 1e-3)])
    @pytest.mark.parametrize("dtype_key", list(DTYPES))
    @pytest.mark.parametrize("ndim", [1, 2, 3])
    @pytest.mark.parametrize("strategy", ["tile", "padded"])
    @pytest.mark.parametrize(
        "backend",
        ["ref", pytest.param("pallas", marks=pytest.mark.slow)])
    def test_lattice_cell(self, backend, strategy, ndim, dtype_key, mode, eb):
        x, c, want = _make_case(ndim, dtype_key, mode, eb)
        cfg = CodecConfig(eb=eb, mode=mode, radius=RADIUS,
                          tile_syms=TILE_SYMS, backend=backend,
                          strategy=strategy)
        fus = Codec(cfg.replace(fused=True))
        fus.backend.reset_stats()
        got = np.asarray(fus.decompress(c))
        assert fus.stats["fused_fallbacks"] == 0
        assert fus.stats["fused_dispatches"] == 1
        assert got.dtype == np.dtype(c.dtype) and got.shape == tuple(c.shape)
        # fused == two-pass on the SAME backend ...
        same = np.asarray(Codec(cfg).decompress(c))
        assert got.tobytes() == same.tobytes()
        # ... == two-pass on the ref backend (the jnp oracle).
        assert got.tobytes() == want.tobytes()
        # And the reconstruction honors the dtype-aware guarantee.
        err = np.abs(got.astype(np.float64) - np.asarray(
            x, np.float32).astype(np.float64)).max()
        assert err <= c.eb_effective

    @pytest.mark.parametrize("strategy", ["tile", "padded"])
    @pytest.mark.parametrize("shape", [(3, 4, 10, 40), (2, 3, 2, 8, 130)])
    def test_beyond_3d_kernel_parity(self, shape, strategy):
        """4-D and 5-D tensors decode fused on the kernels (one HBM carry
        per outer axis), bit-exact with two-pass ref."""
        x = _field(shape, jnp.float32, seed=11)
        cfg = CodecConfig(eb=1e-4, radius=RADIUS, tile_syms=TILE_SYMS)
        c = Codec(cfg).compress(x)
        fus = Codec(cfg.replace(backend="pallas", strategy=strategy,
                                fused=True))
        fus.backend.reset_stats()
        got = np.asarray(fus.decompress(c))
        assert fus.stats["fused_fallbacks"] == 0
        assert fus.stats["fused_dispatches"] == 1
        want = np.asarray(Codec(cfg).decompress(c))
        assert got.tobytes() == want.tobytes()
        assert np.abs(got - np.asarray(x)).max() <= c.eb_effective

    def test_unit_axes_squeeze(self):
        """(1, R, C) reconstructs through the 2-D epilogue, bit-exact."""
        x = _field((1, 56, 72), jnp.float32, seed=5)
        codec = Codec(CodecConfig(eb=1e-4, radius=RADIUS, fused=True,
                                  tile_syms=TILE_SYMS))
        c = codec.compress(x)
        codec.backend.reset_stats()
        got = np.asarray(codec.decompress(c))
        assert codec.stats["fused_fallbacks"] == 0
        want = np.asarray(
            Codec(CodecConfig(eb=1e-4, radius=RADIUS,
                              tile_syms=TILE_SYMS)).decompress(c))
        assert got.tobytes() == want.tobytes()

    def test_acceptance_2d_f32_and_1d_bf16(self):
        """The ISSUE's acceptance cells, spelled out: 2-D float32 and 1-D
        bfloat16 fused decodes are bit-exact with two-pass on both
        backends, dispatches counted, zero fallbacks."""
        for ndim, dtype_key in ((2, "f32"), (1, "bf16")):
            x, c, want = _make_case(ndim, dtype_key, "rel", 1e-4)
            for backend in ("ref", "pallas"):
                cfg = CodecConfig(eb=1e-4, radius=RADIUS,
                                  tile_syms=TILE_SYMS, backend=backend,
                                  fused=True)
                codec = Codec(cfg)
                codec.backend.reset_stats()
                got = np.asarray(codec.decompress(c))
                assert codec.stats["fused_fallbacks"] == 0
                assert codec.stats["fused_dispatches"] == 1
                assert got.tobytes() == want.tobytes()


class TestFusedNdEligibility:
    def test_reasons(self):
        be = hp.get_backend("ref")
        ok = _make_case(2, "f32", "rel", 1e-4)[1]
        assert sz.fused_unsupported_reason(ok, be, "gap", "tile") is None
        assert sz.fused_unsupported_reason(ok, be, "gap", "padded") is None
        assert "tuned" in sz.fused_unsupported_reason(ok, be, "gap", "tuned")
        assert "oracle" in sz.fused_unsupported_reason(
            ok, be, "naive_ref", "tile")
        # 4 non-unit axes: served by the outer carries of the N-D epilogue.
        codec = Codec(CodecConfig(eb=1e-3, radius=RADIUS))
        c4 = codec.compress(smooth_field((4, 5, 6, 8), seed=2))
        assert sz.fused_unsupported_reason(c4, be, "gap", "tile") is None
        # float64 stays two-pass (synthesized: jnp truncates f64 inputs at
        # compress, so a real f64 Compressed never arises on this build).
        import dataclasses

        c64 = dataclasses.replace(ok, dtype=np.dtype(np.float64))
        assert "float64" in sz.fused_unsupported_reason(
            c64, be, "gap", "tile")

    def test_width_bounds(self):
        """Rows past the VMEM row provisioning report a reason; planes have
        no bound, since the plane carry lives in HBM (without paying for a
        huge compress: synthesize the metadata)."""
        be = hp.get_backend("ref")
        base = _make_case(2, "f32", "rel", 1e-4)[1]
        import dataclasses

        wide = dataclasses.replace(
            base, shape=(4, sz.FUSED_MAX_COLS + 1))
        assert "fastest axis" in sz.fused_unsupported_reason(
            wide, be, "gap", "tile")
        deep = dataclasses.replace(base, shape=(4, 2048, 2049))
        assert sz.fused_unsupported_reason(deep, be, "gap", "tile") is None


class TestFallbackAccounting:
    """``fused_fallbacks`` counts each ineligible tensor exactly once, for
    every entry point that can decode many tensors."""

    def _mixed(self):
        codec = Codec(CodecConfig(eb=1e-3, radius=RADIUS))
        wide = sz.FUSED_MAX_COLS + 1
        return codec, [
            codec.compress(smooth_field((3000,), seed=41)),       # eligible
            codec.compress(smooth_field((2, wide), seed=42)),     # wide rows
            codec.compress(smooth_field((20, 25), seed=43)),      # eligible
            codec.compress(smooth_field((3, wide), seed=44)),     # wide rows
        ]

    @pytest.mark.parametrize("strategy", ["tile", "padded"])
    def test_batch_counts_per_tensor(self, strategy):
        _, cs = self._mixed()
        codec = Codec(CodecConfig(eb=1e-3, radius=RADIUS, fused=True,
                                  strategy=strategy))
        codec.backend.reset_stats()
        outs = codec.decompress_batch(cs)
        assert codec.stats["fused_fallbacks"] == 2
        assert codec.stats["fused_dispatches"] == 2
        want = Codec(CodecConfig(eb=1e-3, radius=RADIUS)).decompress_batch(cs)
        for got, ref in zip(outs, want):
            assert np.asarray(got).tobytes() == np.asarray(ref).tobytes()

    def test_single_decompress_counts_once(self):
        _, cs = self._mixed()
        codec = Codec(CodecConfig(eb=1e-3, radius=RADIUS, fused=True))
        codec.backend.reset_stats()
        codec.decompress(cs[1])
        assert codec.stats["fused_fallbacks"] == 1

    def test_tuned_strategy_batch_falls_back_per_tensor(self):
        """With a non-fusable strategy every tensor is ineligible: the
        counter equals the tensor count, not the call count."""
        _, cs = self._mixed()
        codec = Codec(CodecConfig(eb=1e-3, radius=RADIUS, fused=True,
                                  strategy="tuned"))
        codec.backend.reset_stats()
        codec.decompress_batch(cs)
        assert codec.stats["fused_fallbacks"] == len(cs)
        assert codec.stats["fused_dispatches"] == 0


# ---------------------------------------------------------------------------
# Golden vectors: cross-version regression anchors without hypothesis
# ---------------------------------------------------------------------------


def _golden_case(spec):
    x = _field(tuple(spec["shape"]), DTYPES[spec["dtype"]], spec["seed"])
    codec = Codec(CodecConfig(eb=spec["eb"], mode=spec["mode"],
                              radius=spec["radius"],
                              tile_syms=spec["tile_syms"]))
    return x, codec, codec.compress(x)


def _compressed_digest(c) -> str:
    h = hashlib.sha256()
    h.update(np.asarray(c.stream.units).tobytes())
    h.update(np.asarray(c.stream.gaps).tobytes())
    h.update(int(c.stream.total_bits).to_bytes(8, "little"))
    h.update(np.asarray(c.outlier_pos).tobytes())
    h.update(np.asarray(c.outlier_val).tobytes())
    return h.hexdigest()


class TestGoldenVectors:
    def test_golden(self):
        """Compressed bytes AND reconstructions match the checked-in
        fixture: encode and decode are both pinned across versions."""
        with open(GOLDEN_PATH) as f:
            golden = json.load(f)
        assert golden["cases"], "fixture must not be empty"
        for entry in golden["cases"]:
            spec = entry["spec"]
            _, codec, c = _golden_case(spec)
            assert _compressed_digest(c) == entry["compressed_sha256"], \
                f"compressed bytes drifted for {spec}"
            two = np.asarray(codec.decompress(c))
            assert hashlib.sha256(two.tobytes()).hexdigest() == \
                entry["reconstruction_sha256"], \
                f"two-pass reconstruction drifted for {spec}"
            fus = Codec(codec.config.replace(fused=True))
            got = np.asarray(fus.decompress(c))
            assert hashlib.sha256(got.tobytes()).hexdigest() == \
                entry["reconstruction_sha256"], \
                f"fused reconstruction drifted for {spec}"


# ---------------------------------------------------------------------------
# Hypothesis: the same invariant over randomized shapes (when available)
# ---------------------------------------------------------------------------


if HAVE_HYPOTHESIS:
    @settings(max_examples=25, deadline=None)
    @given(
        ndim=st.integers(1, 3),
        dtype_key=st.sampled_from(list(DTYPES)),
        strategy=st.sampled_from(["tile", "padded"]),
        dims=st.lists(st.integers(3, 40), min_size=3, max_size=3),
        seed=st.integers(0, 2**16),
    )
    # A tensor smaller than the spike count of ``_field`` (found by the
    # property search; pinned so it replays without the example database).
    @example(ndim=1, dtype_key="f32", strategy="tile", dims=[3, 3, 3], seed=0)
    def test_fused_parity_property(ndim, dtype_key, strategy, dims, seed):
        shape = tuple(dims[:ndim])
        x = _field(shape, DTYPES[dtype_key], seed)
        cfg = CodecConfig(eb=1e-3, radius=RADIUS, tile_syms=TILE_SYMS,
                          strategy=strategy)
        codec = Codec(cfg)
        c = codec.compress(x)
        want = np.asarray(codec.decompress(c))
        fus = Codec(cfg.replace(fused=True))
        fus.backend.reset_stats()
        got = np.asarray(fus.decompress(c))
        assert fus.stats["fused_fallbacks"] == 0
        assert got.tobytes() == want.tobytes()
