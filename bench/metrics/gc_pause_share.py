"""Host runtime: share of the window that Python's garbage collector held
the process, from the engine's ``gc_pause_s`` counter (a ``gc.callbacks``
hook times every collection).  A pause inside a step delays that tick's
launch or its tokens.  Moves ``output_tok_per_s``."""


def read(ctx):
    if "gc_pause_s" not in ctx.stats or ctx.window.seconds <= 0:
        return None
    return 100.0 * ctx.stats["gc_pause_s"] / ctx.window.seconds
