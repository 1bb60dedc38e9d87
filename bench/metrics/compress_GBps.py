"""Original bytes saved, from device arrays to closed files, per wall second
of the window's compress operations (host clock, all of them, summed)."""


def read(run):
    ops = run.ops_of("compress")
    seconds = sum(o.t1 - o.t0 for o in ops)
    if not ops or seconds <= 0:
        return None
    return sum(o.work.total_bytes for o in ops) / seconds / 1e9
