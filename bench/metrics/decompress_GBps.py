"""Original bytes delivered as ready device arrays per wall second of the
window's decompress operations (host clock, all of them, summed)."""


def read(run):
    ops = run.ops_of("decompress")
    seconds = sum(o.t1 - o.t0 for o in ops)
    if not ops or seconds <= 0:
        return None
    return sum(o.work.total_bytes for o in ops) / seconds / 1e9
