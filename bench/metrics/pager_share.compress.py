"""Percent of the compress spans' wall time in the KV pager's own time: the
``repro.pager.*`` spans less their codec, archive, plan-build and decode
children, on the threads that run the operations
(``bench/spans.py:self_share``).  A program without pager spans reads
nothing."""

from bench import spans


def read(run):
    if run.trace is None:
        return None
    return spans.self_share(spans.of(run), ("repro.pager.",),
                            run.trace.spans_of("compress"))
