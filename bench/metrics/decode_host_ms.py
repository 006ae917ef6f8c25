"""Scheduler: host time of a decode tick outside the device sync, from the
engine's own phase counters over the window: (plan + launch + record) /
decode launches, in ms.  Plan is the free-slot scan, page mapping and
argument transfers; launch the jitted call's dispatch; record the tokens
handed to their requests.  While they run the chip has nothing queued, so
this is the device's idle time per tick.  Moves ``output_tok_per_s``."""


def read(ctx):
    s = ctx.stats
    n = s.get("decode_launches", 0)
    keys = ("decode_plan_s", "decode_launch_s", "decode_record_s")
    if not n or any(k not in s for k in keys):
        return None
    return 1e3 * sum(s[k] for k in keys) / n
