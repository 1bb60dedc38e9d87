"""Share of the tile decode's decoder lanes that carry a real window:
``decode_windows`` over ``decode_steps`` times 1024 (one int32 vector
register of lanes per grid step), in percent, from ``Codec.stats`` over the
window's decompress operations.  A program without these counters reads
nothing."""

VREG_LANES = 1024


def read(run):
    stats = run.stats_of("decompress")
    if not stats or any("decode_steps" not in s for s in stats):
        return None
    steps = sum(s["decode_steps"] for s in stats)
    windows = sum(s["decode_windows"] for s in stats)
    return 100.0 * windows / (steps * VREG_LANES) if steps else None
