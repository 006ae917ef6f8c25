"""Device: peak bytes in use on the chip after the window
(``memory_stats()["peak_bytes_in_use"]``), in GB.  Moves
``output_tok_per_s``: what the weights and caches leave decides how many
slots fit."""


def read(ctx):
    return ctx.mem_peak / 1e9 if ctx.mem_peak else None
