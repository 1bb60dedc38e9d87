"""CPU rehearsals of the serving-cache cell and the warm-plan read cell.

A Kimi-Linear-shaped decode cache (the architecture's reduced preset: 3
KDA layers and 1 MLA layer) goes through ``kv_preempt`` / ``kv_resume`` as
the ``kimi-linear-48b.preempt-resume`` cell runs them, and a tiny field
through ``archive_read_warm``; both are judged by the harness, the
correctness checks' faults are caught, and the control fails.  Pallas
kernels run in interpret mode, so the shapes are small.
"""

import json
import os

import jax.numpy as jnp
import pytest

from bench import harness
from bench.tests import tiny

KIMI = {"name": "tiny-kimi", "source": "test", "reduced": [],
        "input": {"kind": "cache", "dtype": "float32",
                  "arch": "kimi-linear-48b-a3b",
                  "arch_reduced": {"n_layers": 4},
                  "batch": 2, "context": 64, "page_tokens": 16,
                  "base_seed": 5,
                  "tensors": [
                      {"name": "latent", "shape": [1, 2, 64, 48],
                       "token_axis": 2, "scale": 1.0},
                      {"name": "kda_state", "shape": [3, 2, 2, 16, 16],
                       "token_axis": None, "scale": 0.1},
                      {"name": "kda_conv", "shape": [3, 2, 96, 3],
                       "token_axis": None, "scale": 1.0}]},
        "codec": tiny.CODEC, "checks": tiny.CHECKS}

#: the state 4 heads wide, where the program's reduced preset has 2
WIDE = {**KIMI, "name": "tiny-kimi-wide",
        "input": {**KIMI["input"], "tensors": [
            KIMI["input"]["tensors"][0],
            {**KIMI["input"]["tensors"][1], "shape": [3, 2, 4, 16, 16]},
            KIMI["input"]["tensors"][2]]}}

CELLS = {"tiny-kimi.preempt-resume": ("tiny-kimi", "preempt-resume"),
         "tiny-kimi-wide.preempt-resume": ("tiny-kimi-wide",
                                           "preempt-resume"),
         "tiny-field.read-warm": ("tiny-field", "read-warm")}


@pytest.fixture(scope="module")
def layout(tmp_path_factory):
    tmp = str(tmp_path_factory.mktemp("cells"))
    layout = tiny.write_cells(tmp)
    for cfg in (KIMI, WIDE):
        with open(os.path.join(tmp, "configs", cfg["name"] + ".json"),
                  "w") as f:
            json.dump(cfg, f)
    with open(layout.benchmark_json) as f:
        bench = json.load(f)
    bench["workloads"] += [{"name": n, "config": c, "traffic": t,
                            "chips": 1, "why": "test"}
                           for n, (c, t) in CELLS.items()]
    with open(layout.benchmark_json, "w") as f:
        json.dump(bench, f)
    return layout


@pytest.mark.parametrize("cell", ["tiny-kimi.preempt-resume",
                                  "tiny-field.read-warm"])
def test_cell_runs_correct(layout, cell):
    r = tiny.run(layout, cell)
    assert r["correct"], r["checks"]
    assert r["attempted"] >= 2 and r["failed"] == 0
    assert r["checks"]["lattice_mismatch"]["value"] == 0.0


def test_preempt_writes_pages_and_a_snapshot(layout, monkeypatch):
    """Each preemption: 4 latent pages of 16 tokens, one snapshot of the
    two state tensors; each resume stages all five blocks at once."""
    from repro.models import kvcache

    handles, fetched = [], []
    real_preempt, real_fetch = kvcache.preempt, kvcache.KVPager.fetch_many

    def preempt(*args, **kwargs):
        handles.append(real_preempt(*args, **kwargs))
        return handles[-1]

    def fetch_many(self, ids, **kwargs):
        fetched.append(list(ids))
        return real_fetch(self, ids, **kwargs)

    monkeypatch.setattr(kvcache, "preempt", preempt)
    monkeypatch.setattr(kvcache.KVPager, "fetch_many", fetch_many)
    r = tiny.run(layout, "tiny-kimi.preempt-resume")
    assert r["correct"], r["checks"]
    h = handles[0]
    assert len(h.pages) == 4 and h.snapshot is not None
    assert [h.blocks[b]["names"] for b in h.pages] == [["latent"]] * 4
    assert sorted(h.blocks[h.snapshot]["names"]) == ["kda_conv", "kda_state"]
    assert fetched and all(len(ids) == 5 for ids in fetched)


def _one_value_altered(monkeypatch):
    from repro.core.sz import compressor

    real = compressor.decompress_batch

    def broken(cs, *args, **kwargs):
        out = real(cs, *args, **kwargs)
        return [y.reshape(-1).at[y.size // 3].add(2 * c.eb).reshape(y.shape)
                for c, y in zip(cs, out)]

    monkeypatch.setattr(compressor, "decompress_batch", broken)


def test_broken_resume_is_not_correct(layout, monkeypatch):
    _one_value_altered(monkeypatch)
    r = tiny.run(layout, "tiny-kimi.preempt-resume")
    assert not r["correct"], r["checks"]


def test_state_left_out_of_the_resume_is_not_correct(layout, monkeypatch):
    """A resume that brings the pages back but not the snapshot."""
    from repro.models import kvcache

    real = kvcache.resume

    def without_state(pager, handle):
        out = real(pager, handle)
        out["kda_state"] = jnp.zeros_like(out["kda_state"])
        return out

    monkeypatch.setattr(kvcache, "resume", without_state)
    r = tiny.run(layout, "tiny-kimi.preempt-resume")
    assert not r["correct"]


def test_control_fails(layout):
    from bench import control

    c = harness.load_cell(layout, "tiny-kimi.preempt-resume")
    for seed in (1, 2):
        r = control.control_result(c, seed)
        assert r["correct"] is False, r["checks"]
        assert r["checks"]["lattice_mismatch"]["value"] > 0.1


def test_warm_reads_skip_plan_build(layout, monkeypatch):
    """After the warm-up, the window's reads find every plan cached."""
    from repro.core.huffman import pipeline as hp

    builds = []
    real = hp.build_plan

    def counted(*args, **kwargs):
        builds.append(1)
        return real(*args, **kwargs)

    monkeypatch.setattr(hp, "build_plan", counted)
    r = tiny.run(layout, "tiny-field.read-warm", seconds=1.0)
    assert r["correct"], r["checks"]
    assert r["attempted"] >= 2
    assert len(builds) == 1               # the warm-up's read alone


def test_program_cache_spec_must_match(layout):
    """A configuration whose shapes the program's cache_spec does not give
    fails at once."""
    with pytest.raises(ValueError, match="cache_spec"):
        tiny.run(layout, "tiny-kimi-wide.preempt-resume")
