"""The program's own spans in a profiler trace, and their reduction.

The program wraps its layers in ``jax.profiler.TraceAnnotation`` events
named ``repro.<name>`` (``src/repro/runtime/trace.py``; docs/api.md,
"Tracing"), each with the id of the operation it serves as the stat ``op``.
They lie on the host plane, one line per thread, on the device planes'
clock.  ``bench/trace.py`` reads the benchmark's spans and the device's;
this module reads the program's beside them, as
``(name, start, end, thread, op)`` with full names and ``thread`` numbering
the host lines.

The harness hands a reader the reduced trace (``run.trace``), not its
file, so :func:`of` finds the file again: the newest ``.xplane.pb`` under
the harness's working directories (``<tempdir>/bench-*/trace``) whose
``bench.window`` span is the run's.  A program without spans, or a run
without a trace, gives an empty list.
"""

from __future__ import annotations

import bisect
import glob
import os
import tempfile

from bench import trace as T

PROGRAM_PREFIX = "repro."
WINDOW = T.SPAN_PREFIX + "window"

_found: dict = {}


def read(path: str) -> "tuple[list, list]":
    """``(spans, windows)`` of an ``.xplane.pb``: the program's spans,
    sorted by start, and the ``(start, end)`` of each ``bench.window``."""
    from jax.profiler import ProfileData

    spans, windows, thread = [], [], 0
    for plane in ProfileData.from_file(path).planes:
        if T.DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            thread += 1
            for ev in line.events:
                end = ev.start_ns + ev.duration_ns
                if ev.name.startswith(PROGRAM_PREFIX):
                    spans.append((ev.name, ev.start_ns, end, thread,
                                  int(dict(ev.stats).get("op", 0))))
                elif ev.name == WINDOW:
                    windows.append((ev.start_ns, end))
    return sorted(spans, key=lambda s: s[1]), windows


def of(run) -> list:
    """The program's spans in the trace of ``run`` (``[]`` where none)."""
    if run.trace is None:
        return []
    window = sorted(run.trace.spans_of("window"))
    if not window:
        return []
    key = tuple(window)
    if key not in _found:
        pattern = os.path.join(tempfile.gettempdir(), "bench-*", "trace",
                               "**", "*.xplane.pb")
        _found[key] = []
        for path in sorted(glob.glob(pattern, recursive=True),
                           key=os.path.getmtime, reverse=True):
            spans, windows = read(path)
            if sorted(windows) == window:
                _found[key] = spans
                break
    return _found[key]


def subtract(intervals, cut) -> list:
    """The parts of ``intervals`` outside ``cut``."""
    a, b = T.union(intervals), T.union(cut)
    out, j = [], 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k, t = j, s
        while k < len(b) and b[k][0] < e:
            if b[k][0] > t:
                out.append((t, b[k][0]))
            t = max(t, b[k][1])
            k += 1
        if t < e:
            out.append((t, e))
    return out


def main_threads(spans) -> set:
    """The threads that ran the program's operations: for each ``op`` id,
    the thread of its first span (the operation's own, which opens before
    anything it hands to another thread)."""
    first: dict = {}
    for _, s, e, th, op in spans:
        if op and (op not in first or (s, -e) < first[op][:2]):
            first[op] = (s, -e, th)
    return {th for _, _, th in first.values()}


def self_time(spans, names, windows, threads=None) -> float:
    """Nanoseconds inside ``windows`` under a layer's spans, less the part
    covered by their child spans of other layers on the same thread.

    ``names``: the layer's span-name prefixes (``("repro.ckpt.", ...)``);
    ``threads``: the threads to count (default: all).
    """
    names = tuple(names)
    by_thread: dict = {}
    for name, s, e, th, _ in spans:
        if threads is None or th in threads:
            by_thread.setdefault(th, ([], []))[
                0 if name.startswith(names) else 1].append((s, e))
    total = 0.0
    for layer, other in by_thread.values():
        own = T.union(layer)
        starts = [s for s, _ in own]
        children = []
        for s, e in other:
            i = bisect.bisect_right(starts, s) - 1
            if i >= 0 and e <= own[i][1]:
                children.append((s, e))
        total += T.length(T.clip(subtract(own, children), windows))
    return total


def self_share(spans, names, windows) -> "float | None":
    """Percent of ``windows`` in the own time (:func:`self_time`) of the
    layer whose span names start with ``names``, on the threads that run
    the program's operations; None where no such span was recorded."""
    width = T.length(windows)
    if width <= 0 or not any(s[0].startswith(tuple(names)) for s in spans):
        return None
    return 100.0 * self_time(spans, names, windows,
                             main_threads(spans)) / width


def share_under(spans, name, windows) -> "float | None":
    """Percent of ``windows`` under the spans called ``name`` (their
    union, every thread); None where none was recorded."""
    width = T.length(windows)
    under = [(s, e) for n, s, e, _, _ in spans if n == name]
    if width <= 0 or not under:
        return None
    return 100.0 * T.length(T.clip(under, windows)) / width
