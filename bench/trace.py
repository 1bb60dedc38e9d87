"""From a profiler trace to busy time, idle time, top programs and gaps.

The JAX profiler writes an ``.xplane.pb``.  On a TPU each chip is a plane
``/device:TPU:<n>`` with, among others, the lines ``XLA Modules`` (one
event per run of a jitted program, named ``jit_<fn>(<fingerprint>)``) and
``XLA Ops`` (one event per operation inside it).  The benchmark's own host
spans are ``jax.profiler.TraceAnnotation`` events named ``bench.<kind>``
on the host plane.  All planes share one clock, in nanoseconds.

``load`` reads a trace into plain lists; everything after it is arithmetic
on ``(start, end)`` intervals, which the tests check on a recorded trace.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re

SPAN_PREFIX = "bench."
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclasses.dataclass
class Trace:
    """A trace reduced to intervals, in nanoseconds on the trace's clock.

    ``ops[d]``: ``(start, end)`` of every operation on device ``d``.
    ``programs[d]``: ``(name, start, end)`` of every program run on ``d``.
    ``spans``: ``(kind, start, end)`` of the benchmark's host spans.
    """

    ops: dict
    programs: dict
    spans: list

    @property
    def devices(self) -> list:
        return sorted(self.ops)

    def spans_of(self, kind: str) -> list:
        return [(s, e) for k, s, e in self.spans if k == kind]


def program_name(event_name: str) -> str:
    """``jit_decode(123)`` -> ``decode``: the jitted function's name."""
    name = event_name.split("(", 1)[0]
    return name[4:] if name.startswith("jit_") else name


def find_xplane(directory: str) -> str:
    paths = sorted(glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return paths[-1]


def load(path: str) -> Trace:
    """Read an ``.xplane.pb`` (or the newest one under a directory)."""
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        path = find_xplane(path)
    data = ProfileData.from_file(path)
    ops: dict = {}
    programs: dict = {}
    spans: list = []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m is not None and line.name == OPS_LINE:
                ops.setdefault(int(m.group(1)), []).extend(
                    (ev.start_ns, ev.start_ns + ev.duration_ns)
                    for ev in line.events)
            elif m is not None and line.name == MODULES_LINE:
                programs.setdefault(int(m.group(1)), []).extend(
                    (program_name(ev.name), ev.start_ns,
                     ev.start_ns + ev.duration_ns) for ev in line.events)
            elif m is None:
                spans.extend(
                    (ev.name[len(SPAN_PREFIX):], ev.start_ns,
                     ev.start_ns + ev.duration_ns)
                    for ev in line.events if ev.name.startswith(SPAN_PREFIX))
    for d in programs:
        ops.setdefault(d, [])
    return Trace(ops=ops, programs=programs, spans=sorted(spans,
                                                            key=lambda s: s[1]))


# -- interval arithmetic ------------------------------------------------------

def union(intervals) -> list:
    """Sorted, disjoint cover of ``(start, end)`` intervals."""
    out: list = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def clip(intervals, windows) -> list:
    """The parts of ``intervals`` inside any of ``windows`` (both unions)."""
    a, b = union(intervals), union(windows)
    out, j = [], 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            lo, hi = max(s, b[k][0]), min(e, b[k][1])
            if hi > lo:
                out.append((lo, hi))
            k += 1
    return out


def length(intervals) -> float:
    return float(sum(e - s for s, e in union(intervals)))


def busy_ns(trace: Trace, device: int, windows) -> float:
    """Nanoseconds in which an operation ran on ``device`` inside
    ``windows``."""
    return length(clip(trace.ops.get(device, []), windows))


def idle_share(trace: Trace, windows) -> "float | None":
    """``1 - busy / span`` over ``windows``, the mean over the devices."""
    span = length(windows)
    if span <= 0 or not trace.devices:
        return None
    shares = [1.0 - busy_ns(trace, d, windows) / span for d in trace.devices]
    return sum(shares) / len(shares)


def top_programs(trace: Trace, windows, n: int = 10) -> list:
    """``[[name, seconds], ...]``: device time per program inside
    ``windows``, summed over devices, longest first."""
    total: dict = {}
    for d, evs in trace.programs.items():
        for name, s, e in evs:
            t = length(clip([(s, e)], windows))
            if t > 0:
                total[name] = total.get(name, 0.0) + t
    ranked = sorted(total.items(), key=lambda kv: -kv[1])[:n]
    return [[name, t / 1e9] for name, t in ranked]


def idle_gaps(trace: Trace, window, n: int = 10) -> list:
    """``[[label, seconds], ...]``: the longest stretches inside ``window``
    in which device 0 (the lowest) ran nothing, each labelled by the
    benchmark operation span it lies in (``"between spans"`` where none)."""
    if not trace.devices:
        return []
    busy = clip(trace.ops[trace.devices[0]], [window])
    gaps, t = [], window[0]
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if window[1] > t:
        gaps.append((t, window[1]))
    out = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:n]:
        mid = (s + e) / 2
        label = next((k for k, a, b in trace.spans
                      if a <= mid < b and k != "window"), "between spans")
        out.append([label, (e - s) / 1e9])
    return out
