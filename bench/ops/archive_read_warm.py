"""Read the archive ``archive_write`` left through one ``Codec`` kept on
the session from the first read on: after the warm-up its plan cache holds
every chunk's plan, so the window's reads skip plan build.
``Archive(path).read_all()``, ending in ``block_until_ready``; its answer
is every leaf of the archive."""

import os

from bench.work import OpWork

KIND = "decompress"
READS_INPUT = False
ARCHIVE = "data.szt"


def run(s, op: dict):
    import jax

    from bench.session import Outcome
    from repro.store import Archive

    if getattr(s, "warm_codec", None) is None:
        s.warm_codec = s.codec()
    path = os.path.join(s.workdir, ARCHIVE)
    with Archive(path, codec=s.warm_codec) as ar:
        out = ar.read_all()
    jax.block_until_ready(out)
    payload = os.path.getsize(path)
    return Outcome(OpWork(s.values_bytes(), payload, s.values_bytes()),
                   answer=out)
