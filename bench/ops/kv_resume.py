"""Resume the newest preemption: ``kvcache.resume`` through a new
``KVPager`` with a fresh ``Codec`` over its directory (every page and the
snapshot staged and decoded in one ``fetch_many``, then installed into a
zeroed cache), ending in ``block_until_ready``.  Its answer is the cache's
tensors by name."""

from bench.work import OpWork

KIND = "decompress"
READS_INPUT = False


def run(s, op: dict):
    import jax

    from bench.session import Outcome, dir_bytes
    from repro.models import kvcache

    d, handle = s.preempted
    out = kvcache.resume(s.op("kv_preempt").pager(s, d), handle)
    jax.block_until_ready(out)
    return Outcome(OpWork(s.values_bytes(), dir_bytes(d), s.total_bytes()),
                   answer=out)
