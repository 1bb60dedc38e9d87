"""Compress every compressed leaf into one ``.szt`` archive:
``Codec.compress`` of each, added to an ``ArchiveWriter``."""

import os

from bench.work import OpWork

KIND = "compress"
READS_INPUT = True
ARCHIVE = "data.szt"


def run(s, op: dict):
    from bench.session import Outcome
    from repro.store import ArchiveWriter

    codec = s.codec()
    path = os.path.join(s.workdir, ARCHIVE)
    with ArchiveWriter(path, codec=codec) as w:
        for name in sorted(s.compressed):
            w.add(name, codec.compress(s.inputs[name]))
    s.disk_bytes = os.path.getsize(path)
    return Outcome(OpWork(s.values_bytes(), s.disk_bytes, s.values_bytes()))
