"""Save the whole tree as the next checkpoint step:
``CheckpointManager.save(step, params)`` with a fresh ``Codec``."""

import os

from bench.work import OpWork

KIND = "compress"
READS_INPUT = True


def run(s, op: dict):
    from bench.session import Outcome, dir_bytes, nest
    from repro.checkpoint.manager import CheckpointManager

    s.step += 1
    ckpt = os.path.join(s.workdir, "ckpt")
    CheckpointManager(ckpt, codec=s.codec(),
                      compress_min_size=s.min_size).save(s.step,
                                                         nest(s.inputs))
    s.saved_step = s.step
    d = os.path.join(ckpt, f"step_{s.step:08d}")
    s.disk_bytes = dir_bytes(d)
    return Outcome(OpWork(s.values_bytes(), dir_bytes(d, ".szt"),
                          s.total_bytes()))
