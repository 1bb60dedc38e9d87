"""Read the archive ``archive_write`` left: ``Archive(path).read_all()``
with a fresh ``Codec``, ending in ``block_until_ready``.  Its answer is
every leaf of the archive."""

import os

from bench.work import OpWork

KIND = "decompress"
READS_INPUT = False
ARCHIVE = "data.szt"


def run(s, op: dict):
    import jax

    from bench.session import Outcome
    from repro.store import Archive

    path = os.path.join(s.workdir, ARCHIVE)
    with Archive(path, codec=s.codec()) as ar:
        out = ar.read_all()
    jax.block_until_ready(out)
    payload = os.path.getsize(path)
    return Outcome(OpWork(s.values_bytes(), payload, s.values_bytes()),
                   answer=out)
