"""Scheduler: share of the prefill launches' rows that held prompt tokens,
from the engine's counters over the window: ``prefill_tokens`` (prompt
tokens admitted) over ``prefill_slot_tokens`` (launches x slots x
``prefill_len``).  Every admission prefills the full fixed width, so the
rest is padding the chip computes.  Moves ``output_tok_per_s``."""


def read(ctx):
    slots = ctx.stats.get("prefill_slot_tokens", 0)
    if not slots or "prefill_tokens" not in ctx.stats:
        return None
    return 100.0 * ctx.stats["prefill_tokens"] / slots
