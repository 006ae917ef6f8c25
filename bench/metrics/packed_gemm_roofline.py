"""Kernels: share of its roofline that the packed-weight GEMM work
reaches.  The least time of every packed GEMM the window's ticks needed
(``work.gemm_least_s``: packed bytes at each linear's bit assignment,
FLOPs at the tokens really processed) over the device time of the
per-group and fused GEMM kernels' events in the trace.  Moves
``output_tok_per_s``."""
import work


def read(ctx):
    kt = ctx.trace.kernel_s.get("packed_gemm", 0.0)
    if kt <= 0:
        return None
    least = 0.0
    for kind, t0, t1, rows, _ in ctx.window.ticks:
        if kind == "prefill":
            least += work.gemm_least_s(ctx.arch, sum(rows), len(rows),
                                       ctx.peak)
        elif kind == "decode":
            least += work.gemm_least_s(ctx.arch, rows, rows, ctx.peak)
    return 100.0 * least / kt
