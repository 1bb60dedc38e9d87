"""Percent of the compress spans' wall time in the consumer layer's own
time (``consumer_share.decompress`` says what that is)."""

from bench import spans


def read(run):
    if run.trace is None:
        return None
    return spans.self_share(spans.of(run), ("repro.ckpt.", "repro.archive."),
                            run.trace.spans_of("compress"))
