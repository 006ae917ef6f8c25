"""Whole step: model FLOPs of the window over the window's length times
the chip's bf16 peak.  Prompt tokens count as prefilled (not the padded
admission width), decoded tokens one each, attention at each token's real
context.  Moves ``output_tok_per_s``."""
import work


def read(ctx):
    a, w = ctx.arch, ctx.window
    flops = 0
    for kind, _, _, rows, contexts in w.ticks:
        if kind == "prefill":
            flops += sum(work.prefill_flops(a, L) for L in rows)
        elif kind == "decode":
            flops += len(contexts) * work.token_flops(a) + sum(
                work.attn_flops(a, c) for c in contexts)
    if w.seconds <= 0 or flops == 0:
        return None
    return 100.0 * flops / (w.seconds * ctx.peak["flops"])
