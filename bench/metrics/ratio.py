"""Original bytes over the bytes the newest write put on disk."""


def read(run):
    return run.ratio
