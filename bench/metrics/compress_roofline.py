"""Share of the HBM roofline over the compress spans.

Least time: (values read + compressed payload written) over the chip's
HBM bandwidth (``bench/work.py``).  Measured time: the busy time of every
device program inside the benchmark's compress spans, summed over the
devices.
"""

from bench import trace as T
from bench import work


def read(run):
    if run.trace is None:
        return None
    spans = run.trace.spans_of("compress")
    busy = sum(T.busy_ns(run.trace, d, spans) for d in run.trace.devices)
    nbytes = sum(work.bytes_moved(o.work) for o in run.ops_of("compress"))
    return work.roofline_percent(nbytes, busy / 1e9,
                                 run.peaks["hbm_bytes_per_s"])
