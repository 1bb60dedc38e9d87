"""Preempt the victim sequences: ``kvcache.preempt`` of the whole cache
into a new directory, through a ``KVPager`` with a fresh ``Codec`` and the
program's layout of the configuration's architecture.  The latent goes out
in ``page_tokens`` pages, the tensors without a token axis in one snapshot.
The handle is kept for ``kv_resume``; older preemptions are deleted once
the timed span has closed."""

import os
import shutil

from bench.work import OpWork

KIND = "compress"
READS_INPUT = True


def pager(s, directory: str):
    """A ``KVPager`` over ``directory`` with a fresh ``Codec`` and the
    program's cache layout, whose ``cache_spec`` has to give the input's
    shapes (of the architecture's ``reduced(**arch_reduced)`` preset where
    the input names one, for rehearsals)."""
    import jax.numpy as jnp

    from repro import configs
    from repro.models import decode, kvcache

    cfg = configs.get_config(s.spec["arch"])
    if "arch_reduced" in s.spec:
        cfg = cfg.reduced(**s.spec["arch_reduced"])
    spec = decode.cache_spec(cfg, s.spec["batch"], s.spec["context"],
                             jnp.float32)
    got = {k: tuple(shape) for k, (shape, _) in spec.items()}
    if got != s.shapes:
        raise ValueError(f"the program's cache_spec gives {got}, the "
                         f"configuration {s.shapes}")
    return kvcache.KVPager(directory, codec=s.codec(),
                           layout=decode.cache_layout(cfg))


def run(s, op: dict):
    from bench.session import Outcome, dir_bytes
    from repro.models import kvcache

    s.step += 1
    d = os.path.join(s.workdir, f"kv_{s.step:06d}")
    handle = kvcache.preempt(s.inputs, pager(s, d),
                             page_tokens=int(s.spec["page_tokens"]))
    s.disk_bytes = dir_bytes(d)
    old = getattr(s, "preempted", None)
    s.preempted = (d, handle)

    def after():
        if old is not None:
            shutil.rmtree(old[0], ignore_errors=True)

    return Outcome(OpWork(s.values_bytes(), s.disk_bytes, s.total_bytes()),
                   after=after)
