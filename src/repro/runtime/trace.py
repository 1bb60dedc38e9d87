"""Program spans and the transfer and compile counters.

Spans are ``jax.profiler.TraceAnnotation`` events named ``repro.<name>``.
They record nothing unless a profiler session is open
(``jax.profiler.trace(dir)``), so they stay in the code: with no session a
span costs about a microsecond of host time.  A span records host time
only and never waits on the device; around an asynchronous dispatch it
measures the enqueue.  Spans sit in the same ``.xplane.pb`` as the device's
programs, on one clock.

Every span carries ``op``, the id of the top-level operation it serves
(``ckpt.save``, ``ckpt.restore``, ``archive.read_all``; 0 outside any).
The id travels in a ``ContextVar``; work handed to another thread carries
it when submitted through ``contextvars.copy_context().run``.

The counters are process-wide integers, merged into ``Codec.stats``:

* ``h2d_bytes`` / ``d2h_bytes``: bytes moved by :func:`to_device` and
  :func:`to_host`, the transfers at the codec's layer boundaries;
* ``compiles``: programs lowered (``jaxpr_to_mlir_module`` events: every
  program the process had to lower, whether XLA then compiles it or finds
  it in the persistent cache);
* ``compile_ms``: milliseconds in lowering and in backend compilation.

docs/api.md ("Tracing") lists every span.
"""

from __future__ import annotations

import contextlib
import contextvars
import itertools
import threading

import jax
import jax.numpy as jnp
import numpy as np
from jax import monitoring

PREFIX = "repro."
LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

_op = contextvars.ContextVar("repro_trace_op", default=0)
_op_ids = itertools.count(1)
_lock = threading.Lock()
_counts = {"h2d_bytes": 0, "d2h_bytes": 0, "compiles": 0}
_compile_us = 0


def span(label: str, /, **args):
    """Context manager: the span ``repro.<label>``, tagged with the current
    operation's id and ``args``."""
    return jax.profiler.TraceAnnotation(PREFIX + label, op=_op.get(), **args)


@contextlib.contextmanager
def operation(label: str, /, **args):
    """A top-level operation's span: draws a new ``op`` id for every span
    beneath it.  Inside another operation it is a plain span of that one."""
    if _op.get():
        with span(label, **args):
            yield
        return
    token = _op.set(next(_op_ids))
    try:
        with span(label, **args):
            yield
    finally:
        _op.reset(token)


def _add(key: str, n: int):
    with _lock:
        _counts[key] += n


def to_device(x):
    """``jnp.asarray(x)``, counting the bytes placed when ``x`` is on the
    host."""
    if isinstance(x, jax.Array):
        return x
    out = jnp.asarray(x)
    _add("h2d_bytes", out.nbytes)
    return out


def to_host(x, dtype=None) -> np.ndarray:
    """``np.asarray(x, dtype)``, counting the bytes fetched when ``x`` is a
    device array."""
    if isinstance(x, jax.Array):
        _add("d2h_bytes", x.nbytes)
    return np.asarray(x, dtype)


def _on_duration(event: str, duration_secs: float, **kwargs):
    global _compile_us
    if event not in (LOWER_EVENT, COMPILE_EVENT):
        return
    with _lock:
        if event == LOWER_EVENT:
            _counts["compiles"] += 1
        _compile_us += int(duration_secs * 1e6)


monitoring.register_event_duration_secs_listener(_on_duration)


def counters() -> dict:
    """The process-wide counters, all integers."""
    with _lock:
        return {**_counts, "compile_ms": _compile_us // 1000}


def reset_counters():
    global _compile_us
    with _lock:
        for k in _counts:
            _counts[k] = 0
        _compile_us = 0
