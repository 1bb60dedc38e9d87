"""Percent of the decompress spans' wall time under the program's plan
build (``repro.plan.build``: decode phases 1-3 and the CR classes, with
the device-to-host read of the counts that ends it)."""

from bench import spans


def read(run):
    if run.trace is None:
        return None
    return spans.share_under(spans.of(run), "repro.plan.build",
                             run.trace.spans_of("decompress"))
