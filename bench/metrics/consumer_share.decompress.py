"""Percent of the decompress spans' wall time in the consumer layer's own
time: the checkpoint manager's and the archive's spans (``repro.ckpt.*``,
``repro.archive.*``) less their codec, plan-build and decode children, on
the threads that run the operations (``bench/spans.py:self_share``)."""

from bench import spans


def read(run):
    if run.trace is None:
        return None
    return spans.self_share(spans.of(run), ("repro.ckpt.", "repro.archive."),
                            run.trace.spans_of("decompress"))
