"""Decode-write dispatches per decompress operation, from the codec's
``decode_write_dispatches`` counter.  A fused decode counts once there (it
also bumps ``fused_dispatches``, which is therefore not added)."""


def read(run):
    counts = [s.get("decode_write_dispatches", 0)
              for s in run.stats_of("decompress")]
    if not counts:
        return None
    return sum(counts) / len(counts)
