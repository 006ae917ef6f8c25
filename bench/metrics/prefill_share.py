"""Model step: share of the window spent inside admission ticks (the
fixed-width prefill launch and its host sync), from the harness's spans
around ``step()``.  Moves ``itl_p99_ms``: every admission stalls the live
slots' next token."""


def read(ctx):
    w = ctx.window
    t = sum(t1 - t0 for kind, t0, t1, _, _ in w.ticks if kind == "prefill")
    return 100.0 * t / w.seconds if w.seconds > 0 else None
