"""Restore the newest checkpoint step: ``CheckpointManager.restore()`` with
a fresh ``Codec``, ending in ``block_until_ready``.  Its answer is every
leaf, and the step it restored."""

import os
import shutil

from bench.work import OpWork

KIND = "decompress"
READS_INPUT = False


def run(s, op: dict):
    import jax

    from bench.session import Outcome, dir_bytes, flatten
    from repro.checkpoint.manager import CheckpointManager

    ckpt = os.path.join(s.workdir, "ckpt")
    res = CheckpointManager(ckpt, codec=s.codec()).restore()
    out = flatten(res["params"]) if res else {}
    jax.block_until_ready(out)
    step = res["step"] if res else None
    d = os.path.join(ckpt, f"step_{step:08d}") if res else None
    payload = dir_bytes(d, ".szt") if d else 0

    def after():
        # Steps older than the one restored are not read again.
        for name in os.listdir(ckpt):
            if name.startswith("step_") and step is not None and (
                    name.endswith(".tmp") or int(name[5:13]) < step):
                shutil.rmtree(os.path.join(ckpt, name), ignore_errors=True)

    return Outcome(OpWork(s.values_bytes(), payload, s.total_bytes()),
                   answer=out, step=step, after=after)
