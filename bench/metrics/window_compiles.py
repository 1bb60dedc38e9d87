"""Programs lowered inside the window's operations: the sum of the
``compiles`` counter of ``Codec.stats`` over them.  The warm-up runs every
operation once, so a program lowered here is one a shape or a constant
made new."""


def read(run):
    if not run.stats or any("compiles" not in s for s in run.stats):
        return None
    return sum(s["compiles"] for s in run.stats)
