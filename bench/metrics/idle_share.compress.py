"""Percent of the compress spans in which the device ran nothing, the mean
over the cell's devices."""

from bench import trace as T


def read(run):
    if run.trace is None:
        return None
    share = T.idle_share(run.trace, run.trace.spans_of("compress"))
    return None if share is None else 100.0 * share
