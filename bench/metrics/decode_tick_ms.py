"""Model step: mean wall time of a decode tick (launch, device work and
the host sync on its tokens), from the harness's spans around ``step()``.
Moves ``output_tok_per_s``."""


def read(ctx):
    d = [t1 - t0 for kind, t0, t1, _, _ in ctx.window.ticks
         if kind == "decode"]
    return 1e3 * sum(d) / len(d) if d else None
