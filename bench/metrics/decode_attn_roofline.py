"""Kernels: share of its roofline that decode attention reaches.  The
least time of attending each live slot to its own positions in every
decode tick (``work.decode_attn_least_s``: packed K/V bytes and scales up
to each slot's position) over the device time of the decode-attention
kernel's events in the trace.  Moves ``output_tok_per_s``."""
import work


def read(ctx):
    kt = ctx.trace.kernel_s.get("decode_attn", 0.0)
    if kt <= 0:
        return None
    least = sum(work.decode_attn_least_s(ctx.arch, contexts, ctx.peak)
                for kind, _, _, _, contexts in ctx.window.ticks
                if kind == "decode")
    return 100.0 * least / kt
