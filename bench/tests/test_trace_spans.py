"""The reduction of the program's spans (``bench/spans.py``): own time of a
layer and the threads that run operations on hand-made spans, and the
spans found again from a run's reduced trace on a CPU profiler trace."""

import os
import tempfile
import types

import jax
import jax.numpy as jnp
import pytest

from bench import spans as S
from bench import trace as T

CONSUMER = ("repro.ckpt.", "repro.archive.")


def _spans():
    """One restore (op 1) on thread 1, its staging on thread 2.

    Thread 1: ckpt.restore 0-100 > archive.read_all 10-90 > plan.build
    20-40, decode.dispatch 50-60, archive.cast 60-65.  Thread 2 (prefetch):
    archive.stage 22-30.
    """
    return [("repro.ckpt.restore", 0, 100, 1, 1),
            ("repro.archive.stage", 22, 30, 2, 1),
            ("repro.archive.read_all", 10, 90, 1, 1),
            ("repro.plan.build", 20, 40, 1, 1),
            ("repro.decode.dispatch", 50, 60, 1, 1),
            ("repro.archive.cast", 60, 65, 1, 1)]


def test_no_spans_read_as_absent():
    assert S.main_threads([]) == set()
    assert S.self_share([], CONSUMER, [(0, 10)]) is None
    assert S.share_under([], "repro.plan.build", [(0, 10)]) is None
    assert S.of(types.SimpleNamespace(trace=None)) == []


def test_subtract():
    assert S.subtract([(0, 10), (20, 30)], [(5, 25)]) == [(0, 5), (25, 30)]
    assert S.subtract([(0, 10)], [(2, 3), (4, 6)]) == [(0, 2), (3, 4),
                                                        (6, 10)]
    assert S.subtract([(0, 10)], []) == [(0, 10)]
    assert S.subtract([(0, 10)], [(0, 10)]) == []


def test_main_threads_are_those_of_the_operations():
    assert S.main_threads(_spans()) == {1}


def test_self_time_less_children_of_other_layers():
    sp = _spans()
    # Thread 1: 0-100 consumer, less plan.build (20) and decode.dispatch
    # (10); archive.cast is the consumer's own.
    assert S.self_time(sp, CONSUMER, [(0, 120)], {1}) == 70
    assert S.self_time(sp, CONSUMER, [(0, 50)], {1}) == 30
    # Every thread: the prefetch thread's staging adds its 8.
    assert S.self_time(sp, CONSUMER, [(0, 120)]) == 78
    # Plan build alone, a layer with no children.
    assert S.self_time(sp, ("repro.plan.",), [(0, 120)]) == 20
    assert S.self_share(sp, CONSUMER, [(0, 100)]) == pytest.approx(70.0)


def test_share_under_clips_to_the_windows():
    sp = _spans()
    assert S.share_under(sp, "repro.plan.build", [(0, 100)]) == 20.0
    assert S.share_under(sp, "repro.plan.build", [(30, 50)]) == 50.0


def _record(directory, with_program_span):
    """A profiler trace of one ``bench.window`` span around a jitted add,
    under ``repro.archive.read_all`` (op 7) where asked."""
    with jax.profiler.trace(directory):
        with jax.profiler.TraceAnnotation("bench.window"):
            if with_program_span:
                with jax.profiler.TraceAnnotation("repro.archive.read_all",
                                                  op=7):
                    jnp.add(jnp.ones(4), 1).block_until_ready()
            else:
                jnp.add(jnp.ones(4), 1).block_until_ready()
    return types.SimpleNamespace(trace=T.load(directory))


def test_of_finds_the_run_trace_among_the_working_directories(monkeypatch):
    with tempfile.TemporaryDirectory() as tmp:
        monkeypatch.setattr(tempfile, "tempdir", tmp)
        old = _record(os.path.join(tmp, "bench-a", "trace"), True)
        new = _record(os.path.join(tmp, "bench-b", "trace"), False)
        found = S.of(old)
        assert [(n, op) for n, _, _, _, op in found] == [
            ("repro.archive.read_all", 7)]
        (window,) = old.trace.spans_of("window")
        assert window[0] <= found[0][1] <= found[0][2] <= window[1]
        # The newer trace holds no program span: nothing, not the older's.
        assert S.of(new) == []
        # A window no file holds reads as no spans.
        t = T.Trace(ops={}, programs={}, spans=[("window", 1, 2)])
        assert S.of(types.SimpleNamespace(trace=t)) == []
