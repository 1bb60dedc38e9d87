"""Bytes the program moved between host and device (``h2d_bytes`` +
``d2h_bytes`` of ``Codec.stats``) per byte of compressed values, over the
window's decompress operations."""


def read(run):
    stats = run.stats_of("decompress")
    if not stats or any("h2d_bytes" not in s for s in stats):
        return None
    moved = sum(s["h2d_bytes"] + s["d2h_bytes"] for s in stats)
    values = sum(o.work.values_bytes for o in run.ops_of("decompress"))
    return moved / values if values else None
