"""A Kimi-Linear-shaped hybrid decode cache through the pager: latent pages
beside whole-state snapshots (``store/paging.py``, ``models/kvcache.py``).

The cache is the architecture's reduced preset at 4 layers (3 KDA : 1
MLA), batch 2, 256 tokens, on seeded random values.  The plain reference
is dual quantization written out in NumPy with one bound per whole tensor:
``x' = float32(round(x / s)) * s``, ``s = float32(2 * float32(eb *
(max(x) - min(x))))``.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.core import Codec, CodecConfig
from repro.models import decode as D
from repro.models import kvcache
from repro.runtime import trace
from repro.store import Archive, KVPager

KIMI = configs.get_config("kimi-linear-48b-a3b")
SMALL = KIMI.reduced(n_layers=4)
EB = 1e-3
#: the device encode path the benchmark runs, with jnp kernels on the CPU
CFG = CodecConfig(eb=EB, encode_backend="jnp", backend="ref", fused=True)
LAYOUT = D.cache_layout(SMALL)


def _codec():
    return Codec(CFG)


def _cache(seed=0, sign=1.0, batch=2, tokens=256):
    spec = D.cache_spec(SMALL, batch, tokens, jnp.float32)
    key = jax.random.PRNGKey(seed)
    return {k: sign * jax.random.normal(jax.random.fold_in(key, i), shape,
                                        dt)
            for i, (k, (shape, dt)) in enumerate(sorted(spec.items()))}


def _reference(x, eb=EB):
    x = np.asarray(x, np.float32)
    span = float(np.float32(x.max()) - np.float32(x.min()))
    s = np.float32(2 * np.float32(eb * span))
    return np.round(x / s).astype(np.float32) * s


def test_cache_spec_of_the_published_config():
    assert KIMI.kda_layers == (1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17,
                               18, 19, 21, 22, 23, 25, 26)
    assert KIMI.mla_layers == (4, 8, 12, 16, 20, 24, 27)
    spec = D.cache_spec(KIMI, 4, 4096, jnp.float32)
    n_mla, n_kda = len(KIMI.mla_layers), len(KIMI.kda_layers)
    assert {k: tuple(s) for k, (s, _) in spec.items()} == {
        "latent": (n_mla, 4, 4096, 512 + 64),
        "kda_state": (n_kda, 4, 32, 128, 128),
        "kda_conv": (n_kda, 4, 3 * 32 * 128, 4 - 1)}
    assert all(dt == jnp.float32 for _, dt in spec.values())
    nbytes = sum(int(np.prod(s)) * 4 for s, _ in spec.values())
    assert nbytes == 443_809_792                       # 444 MB
    state = sum(int(np.prod(spec[k][0])) * 4
                for k in ("kda_state", "kda_conv"))
    assert 0.40 < state / nbytes < 0.41
    assert D.cache_layout(KIMI) == {"latent": 2, "kda_state": None,
                                    "kda_conv": None}
    # the latent takes the compute dtype unless told otherwise
    assert D.cache_spec(KIMI, 1, 8)["latent"][1] == KIMI.cdt


def test_preempt_resume_equals_the_reference(tmp_path):
    cache = _cache()
    pager = KVPager(str(tmp_path), codec=_codec(), layout=LAYOUT)
    handle = kvcache.preempt(cache, pager, page_tokens=64)
    assert len(handle.pages) == 4 and handle.snapshot is not None
    fresh = KVPager(str(tmp_path), codec=_codec(), layout=LAYOUT)
    out = kvcache.resume(fresh, handle)
    assert set(out) == set(cache)
    for name, x in cache.items():
        got = np.asarray(out[name])
        assert got.dtype == np.float32 and got.shape == x.shape
        np.testing.assert_array_equal(got, _reference(x), err_msg=name)
    # One lattice for the whole tensor: the pages' own ranges would have
    # quantized the latent otherwise.
    lat = np.asarray(cache["latent"])
    own = np.concatenate([_reference(lat[:, :, lo:lo + 64])
                          for lo in range(0, 256, 64)], axis=2)
    assert not np.array_equal(own, np.asarray(out["latent"]))


def test_preempt_resume_stats_and_host_copies(tmp_path):
    cache = _cache(seed=1)
    pager = KVPager(str(tmp_path), codec=_codec(), layout=LAYOUT)
    d2h = trace.counters()["d2h_bytes"]
    handle = kvcache.preempt(cache, pager, page_tokens=100)
    moved = trace.counters()["d2h_bytes"] - d2h
    raw = {k: int(v.nbytes) for k, v in cache.items()}
    # the pager's host copies are counted: every value once, at least
    assert moved >= sum(raw.values())
    st = pager.stats
    assert (st["pages_out"], st["snapshots_out"]) == (3, 1)  # 100+100+56
    assert st["bytes_raw"] == raw["latent"]
    assert st["snapshot_bytes_raw"] == raw["kda_state"] + raw["kda_conv"]
    assert st["snapshot_bytes_compressed"] == os.path.getsize(
        pager.block_meta(handle.snapshot)["path"])
    out = kvcache.resume(pager, handle)
    assert (pager.stats["pages_in"], pager.stats["snapshots_in"]) == (3, 1)
    for name, x in cache.items():
        np.testing.assert_array_equal(np.asarray(out[name]), _reference(x))
    assert pager.ratio > 1.0


def test_offload_alone_bounds_each_page_by_its_own_range(tmp_path):
    """``offload`` without ``value_ranges`` keeps the per-page bound; with
    them every page sits on the whole tensor's lattice."""
    lat = _cache(seed=5)["latent"]
    pager = KVPager(str(tmp_path), codec=_codec(), layout=LAYOUT)
    ranges = pager.value_ranges({"latent": lat}, ["latent"])
    assert ranges == {"latent": float(jnp.max(lat) - jnp.min(lat))}
    _, own = pager.offload({"latent": lat}, 64, 128)
    _, whole = pager.offload({"latent": lat}, 64, 128, value_ranges=ranges)
    page = np.asarray(lat)[:, :, 64:128]
    np.testing.assert_array_equal(
        np.asarray(pager.fetch(own)["latent"]), _reference(page))
    x = np.asarray(lat)
    s = np.float32(2 * np.float32(EB * float(np.float32(x.max())
                                             - np.float32(x.min()))))
    np.testing.assert_array_equal(
        np.asarray(pager.fetch(whole)["latent"]),
        np.round(page / s).astype(np.float32) * s)


def test_value_range_only_under_a_relative_bound(tmp_path):
    x = jnp.arange(12.0).reshape(3, 4)
    assert _codec().value_range(x) == 11.0
    absolute = Codec(CodecConfig(eb=0.01, mode="abs", encode_backend="jnp"))
    assert absolute.value_range(x) is None
    pager = KVPager(str(tmp_path), codec=absolute, layout=LAYOUT)
    assert pager.value_ranges({"latent": x}, ["latent"]) == {"latent": None}


def test_state_is_never_sliced_by_a_page(tmp_path):
    """A state whose head axis (axis 2) is at least as long as the page
    end: the layout keeps it out of every page, whole in the snapshot."""
    key = jax.random.PRNGKey(3)
    cache = {"latent": jax.random.normal(key, (2, 1, 16, 8)),
             "kda_state": jax.random.normal(key, (3, 1, 16, 4, 4))}
    layout = {"latent": 2, "kda_state": None}
    pager = KVPager(str(tmp_path / "a"), codec=_codec(), layout=layout)
    out, bid = pager.offload(dict(cache), 0, 8)
    assert pager.block_meta(bid)["names"] == ["latent"]
    np.testing.assert_array_equal(np.asarray(out["kda_state"]),
                                  np.asarray(cache["kda_state"]))
    assert np.all(np.asarray(out["latent"])[:, :, :8] == 0)
    handle = kvcache.preempt(cache, pager, page_tokens=8)
    assert all(pager.block_meta(b)["names"] == ["latent"]
               for b in handle.pages)
    with Archive(pager.block_meta(handle.snapshot)["path"]) as ar:
        assert ar.names == ["kda_state"]
        assert tuple(ar.chunk("kda_state").shape) == (3, 1, 16, 4, 4)
    with pytest.raises(ValueError):
        pager.offload(dict(cache), 0, 8, keys=["kda_state"])
    # Without a layout every tensor is paged on axis 2: the heads as tokens.
    legacy = KVPager(str(tmp_path / "b"), codec=_codec())
    _, bid = legacy.offload(dict(cache), 0, 8)
    assert legacy.block_meta(bid)["names"] == ["latent", "kda_state"]


def _sizes(pager, handle):
    return [os.path.getsize(pager.block_meta(b)["path"])
            for b in handle.block_ids]


def test_repeated_preemptions_give_the_same_payloads(tmp_path):
    cache = _cache(seed=2)
    pager = KVPager(str(tmp_path), codec=_codec(), layout=LAYOUT)
    first = kvcache.preempt(cache, pager, page_tokens=64)
    again = kvcache.preempt(cache, pager, page_tokens=64)
    assert _sizes(pager, first) == _sizes(pager, again)
    keys = [pager.block_key(b) for b in first.block_ids]
    assert keys == [pager.block_key(b) for b in again.block_ids]
    # The seed's other sign: the same magnitudes, mirrored residuals.
    flipped = kvcache.preempt(_cache(seed=2, sign=-1.0), pager,
                              page_tokens=64)
    a, b = sum(_sizes(pager, first)), sum(_sizes(pager, flipped))
    assert abs(a - b) <= 1e-3 * a


def test_bf16_bytes_counted_from_the_dtype(tmp_path):
    k = jnp.cumsum(jax.random.normal(jax.random.PRNGKey(4), (2, 1, 32, 2, 8)),
                   axis=2).astype(jnp.bfloat16)
    pager = KVPager(str(tmp_path), codec=_codec())
    _, bid = pager.offload({"k": k}, 0, 16)
    assert pager.stats["bytes_raw"] == k.size // 2 * 2     # 2 B a value
    staged = pager.stage(bid)
    assert staged.decoded_bytes == k.size // 2 * 4         # decoded f32
    cc = kvcache.compress_cache({"k": k.astype(jnp.float32)}, codec=_codec())
    assert cc.original_bytes == k.size * 4


def test_serve_pages_only_tensors_with_a_token_axis(tmp_path):
    from repro.launch import serve

    out = serve.main(["--arch", "kimi-linear-48b-a3b", "--reduced",
                      "--batch", "1", "--prompt-len", "8", "--gen-len", "2",
                      "--kv-offload", "--kv-block", "4",
                      "--kv-offload-dir", str(tmp_path)])
    st = out["page_stats"]
    assert (st["pages_out"], st["pages_in"], st["pages_lost"]) == (2, 2, 0)
    assert st["snapshots_out"] == 0
    assert out["tokens"].shape == (1, 3)
