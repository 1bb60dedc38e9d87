"""CPU rehearsals of whole runs: a cell added as files alone, and the
faults the correctness check has to catch.

Each test writes tiny configurations and traffic mixes into a temporary
directory (``tiny.py``), then drives a cell through ``harness.run_cell``
with the look for a TPU skipped: Pallas kernels run in interpret mode and
no metric is reported.  The fault tests break the timed path underneath
(the program's decode or save) and see ``correct`` come out false.
"""

import json
import os
import subprocess
import sys

import jax.numpy as jnp
import pytest

from bench import harness, session
from bench.tests import tiny

CELLS = sorted(tiny.CELLS)


@pytest.fixture(scope="module")
def layout(tmp_path_factory):
    return tiny.write_cells(str(tmp_path_factory.mktemp("cells")))


@pytest.mark.parametrize("cell", CELLS)
def test_cell_added_as_files_runs_correct(layout, cell):
    r = tiny.run(layout, cell)
    assert r["correct"], r["checks"]
    assert r["attempted"] >= 1 and r["failed"] == 0
    assert r["metrics"] == {}                  # no device metric off the chip
    assert list(r)[-1] == "checks"
    assert r["device"]["platform"] == "cpu"
    assert r["checks"]["err_over_eb"]["value"] <= 1.0


def _decode_patch(monkeypatch, change):
    from repro.core.sz import compressor

    real = compressor.decompress_batch

    def broken(cs, *args, **kwargs):
        return [change(c, y) for c, y in zip(cs, real(cs, *args, **kwargs))]

    monkeypatch.setattr(compressor, "decompress_batch", broken)


def _one_value_altered(c, y):
    flat = y.reshape(-1)
    return flat.at[flat.shape[0] // 3].add(2 * c.eb).reshape(y.shape)


def _half_left_out(c, y):
    flat = y.reshape(-1)
    n = flat.shape[0]
    return jnp.where(jnp.arange(n) < n // 2, flat, 0).reshape(y.shape)


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("fault", ["altered", "half"])
def test_broken_decode_is_not_correct(layout, monkeypatch, cell, fault):
    _decode_patch(monkeypatch, {"altered": _one_value_altered,
                                "half": _half_left_out}[fault])
    r = tiny.run(layout, cell)
    assert not r["correct"], r["checks"]


def test_restore_missing_half_the_leaves_is_not_correct(layout, monkeypatch):
    from repro.checkpoint import manager

    real = manager.CheckpointManager.restore

    def broken(self, *args, **kwargs):
        res = real(self, *args, **kwargs)
        flat = session.flatten(res["params"])
        res["params"] = dict(sorted(flat.items())[: len(flat) // 2])
        return res

    monkeypatch.setattr(manager.CheckpointManager, "restore", broken)
    r = tiny.run(layout, "tiny-tree.save-restore")
    assert not r["correct"]
    assert r["checks"]["missing"]["value"] > 0


def test_save_that_keeps_the_old_state_is_not_correct(layout, monkeypatch):
    """A save that returns without writing its step: the restore that
    follows reads an older step."""
    from repro.checkpoint import manager

    real = manager.CheckpointManager.save
    calls = []

    def stale(self, step, *args, **kwargs):
        calls.append(step)
        if len(calls) == 1:
            return real(self, step, *args, **kwargs)
        return None

    monkeypatch.setattr(manager.CheckpointManager, "save", stale)
    r = tiny.run(layout, "tiny-tree.save-restore", seconds=1.0)
    assert not r["correct"]
    assert r["checks"]["stale"]["value"] > 0


@pytest.mark.parametrize("cell", CELLS)
def test_control_fails_every_seed(layout, cell):
    """The bfloat16 reference in the program's place, judged by the
    function that decides a run's ``correct``."""
    from bench import control

    c = harness.load_cell(layout, cell)
    for seed in (1, 2, 3):
        r = control.control_result(c, seed)
        assert r["correct"] is False, r["checks"]
        assert r["checks"]["lattice_mismatch"]["value"] > 0.1


_KIND = '''
"""Two leaves of integrated noise of shapes that are no cube."""
from bench.session import seed_key

def shapes(spec, chips):
    return {"a": (12, 20), "b": (6, 10, 14)}

def make(spec, seed, chips=1):
    import jax, jax.numpy as jnp
    out = {}
    for i, (name, shape) in enumerate(shapes(spec, chips).items()):
        x = jax.random.normal(jax.random.fold_in(seed_key(seed), i), shape)
        for ax in range(len(shape)):
            x = jnp.cumsum(x, axis=ax)
        out[name] = x / jnp.max(jnp.abs(x))
    return out
'''

_OP = '''
"""Read the archive leaf by leaf, each with a fresh Codec."""
import os
from bench.work import OpWork

KIND = "decompress"
READS_INPUT = False

def run(s, op):
    import jax
    from bench.session import Outcome
    from repro.store import Archive
    path = os.path.join(s.workdir, "data.szt")
    out = {}
    for name in sorted(s.compressed):
        with Archive(path, codec=s.codec()) as ar:
            out.update({k: v for k, v in ar.read_all().items() if k == name})
    jax.block_until_ready(out)
    return Outcome(OpWork(s.values_bytes(), os.path.getsize(path),
                          s.values_bytes()), answer=out)
'''


def test_input_kind_and_operation_added_as_files(tmp_path):
    """A cell whose input kind and window operation are new files under a
    root of their own runs correct; nothing under ``bench/`` is edited."""
    layout = tiny.write_cells(str(tmp_path))
    for sub, name, text in (("inputs", "pair", _KIND),
                            ("ops", "archive_read_each", _OP)):
        os.makedirs(tmp_path / sub, exist_ok=True)
        (tmp_path / sub / (name + ".py")).write_text(text)
    cfg = {**tiny.FIELD, "name": "tiny-pair",
           "input": {"kind": "pair", "dtype": "float32"}}
    (tmp_path / "configs" / "tiny-pair.json").write_text(json.dumps(cfg))
    (tmp_path / "traffic" / "tiny-read-each.json").write_text(json.dumps(
        {"setup": [{"op": "archive_write"}],
         "window": [{"op": "archive_read_each"}]}))
    bench = json.loads((tmp_path / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": "tiny-pair.read-each",
                               "config": "tiny-pair",
                               "traffic": "tiny-read-each", "chips": 1,
                               "why": "test"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    r = tiny.run(layout, "tiny-pair.read-each")
    assert r["correct"], r["checks"]
    assert r["attempted"] >= 1 and r["checks"]["missing"]["value"] == 0


def test_run_without_tpu_exits_nonzero_and_prints_no_result():
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "nyx-512.read",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=harness.REPO_DIR, capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert p.returncode != 0
    assert p.stdout.strip() == ""
