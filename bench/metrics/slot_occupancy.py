"""Scheduler: mean share of slots live in a decode tick, from the engine's
own counters over the window (``occupancy_sum / decode_launches``).
Moves ``output_tok_per_s``."""


def read(ctx):
    n = ctx.stats.get("decode_launches", 0)
    return 100.0 * ctx.stats["occupancy_sum"] / n if n else None
