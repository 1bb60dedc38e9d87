"""The plain reference agrees with the codec, and its control does not."""

import numpy as np
import pytest

from bench import reference

SPEC = {"kind": "tree", "compress_min_size": 100}


def _field(seed=0, shape=(8, 16, 32)):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal(shape)
    for ax in range(len(shape)):
        x = np.cumsum(x, axis=ax)
    return (x / np.abs(x).max()).astype(np.float32)


def _codec_round_trip(x, eb=1e-3):
    from repro.core import Codec, CodecConfig

    codec = Codec(CodecConfig(eb=eb, mode="rel", backend="ref",
                              encode_backend="jnp", fused=False))
    return np.asarray(codec.decompress(codec.compress(x)))


def test_reference_equals_codec_on_cpu():
    x = _field()
    t = reference.compare({"f": x}, {"f": _codec_round_trip(x)},
                          {"kind": "field"}, 1e-3)
    assert t.missing == 0 and t.lattice_mismatch == 0.0
    assert 0.9 < t.err_over_eb <= 1.0


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_bfloat16_control_fails(seed):
    x = _field(seed)
    y = reference.control_answer(x, True, 1e-3)
    t = reference.compare({"f": x}, {"f": y}, {"kind": "field"}, 1e-3)
    assert t.err_over_eb > 1.0
    assert t.lattice_mismatch > 0.1


def test_tally_counts_each_fault():
    x = _field(4)
    good = reference.control_answer(x, True, 1e-3, precision="float32")
    raw = np.arange(10, dtype=np.float32)
    inputs = {"f": x, "r": raw}

    t = reference.compare(inputs, {"f": good, "r": raw}, SPEC, 1e-3)
    assert (t.missing, t.raw_mismatch, t.lattice_bad) == (0, 0, 0)

    off = good.copy()
    off.reshape(-1)[5] += 2 * reference.bound(x, 1e-3)[0]
    t = reference.compare(inputs, {"f": off, "r": raw}, SPEC, 1e-3)
    assert t.lattice_bad == 1 and t.err_over_eb > 1.0

    t = reference.compare(inputs, {"f": good}, SPEC, 1e-3)
    assert t.missing == 1
    t = reference.compare(inputs, {"f": good, "r": raw + 1}, SPEC, 1e-3)
    assert t.raw_mismatch == 10
    t = reference.compare(inputs, {"f": good[:4], "r": raw}, SPEC, 1e-3)
    assert t.missing == 1
    nan = good.copy()
    nan.reshape(-1)[0] = np.nan
    t = reference.compare(inputs, {"f": nan, "r": raw}, SPEC, 1e-3)
    assert t.err_over_eb == np.inf


def test_blocks_cover_a_tensor_larger_than_one_block(monkeypatch):
    monkeypatch.setattr(reference, "BLOCK", 1000)
    x = _field(5)
    good = reference.control_answer(x, True, 1e-3, precision="float32")
    t = reference.compare({"f": x}, {"f": good}, {"kind": "field"}, 1e-3)
    assert t.lattice_n == x.size and t.lattice_bad == 0
    bad = good.copy()
    bad.reshape(-1)[-1] += 1.0
    t = reference.compare({"f": x}, {"f": bad}, {"kind": "field"}, 1e-3)
    assert t.lattice_bad == 1
