"""The comparison that decides ``correct``.

Once the window has closed and the program's state is freed, a sample of
the requests the engine finished inside the window, drawn from the seed,
holding the longest of them and at least the mix's ``min_tokens`` served
tokens, is run through the plain reference (``reference.py``) over each
prompt and its served tokens.  At each
served position the gap is how far the served token's reference logit
lies below the reference's best logit there: greedy serving that computes
what the configuration states keeps it small, a lower precision widens
it.  A cell's limits file (``limits/<cell>.json``) names the numbers
compared: the widest gap, the mean gap, or both; PERF.md gives the
readings each limit was set from.  Every served request must also hold
exactly the tokens it asked for, each inside the vocabulary.
"""
from __future__ import annotations

import numpy as np

import reference


def sample(finished: list, seed: int, n: int, min_tokens: int = 0) -> list:
    """The longest finished request and others drawn from the seed: at
    least ``n`` requests, and more until they hold ``min_tokens`` served
    tokens (or every finished request is in)."""
    if not finished:
        return []
    order = sorted(finished, key=lambda r: (-len(r.tokens), r.rid))
    rest = order[1:]
    rng = np.random.default_rng(int(seed) ^ 0x5EED)
    pick, held = [], len(order[0].tokens)
    for i in rng.permutation(len(rest)):
        if len(pick) + 1 >= n and held >= min_tokens:
            break
        pick.append(i)
        held += len(rest[i].tokens)
    return [order[0]] + [rest[i] for i in sorted(pick)]


def served(arch, seed: int, finished: list, limits: dict, mix: dict,
           controls: tuple = ()) -> dict:
    """``correct`` for the served sample: every number that the cell's
    limits file names (``widest_gap``, ``mean_gap``) at or under its
    limit.  With ``controls`` (bit widths) each control is read too, and
    the first stands in the program's place: the verdict is its, the
    program's own readings are kept beside it."""
    chosen = sample(finished, seed, int(mix["check"]["requests"]),
                    int(mix["check"]["min_tokens"]))
    bad = sum(1 for r in chosen
              if len(r.tokens) != r.max_tokens
              or not ((r.tokens >= 0) & (r.tokens < arch.vocab_size)).all())
    checks = {"bad_outputs": {"value": bad, "limit": 0}}
    if not chosen or bad:
        return {"correct": False, "checks": checks}
    pairs = [(r.prompt, r.tokens) for r in chosen]
    g = reference.gaps(arch, seed, pairs)
    checks["tokens_compared"] = {"value": g["tokens"],
                                 "limit": mix["check"]["min_tokens"]}
    ok = g["tokens"] >= mix["check"]["min_tokens"]
    if controls:
        checks["program_stats"] = {"value": g, "limit": None}
        stats = {b: reference.gaps(arch, seed, pairs, control_bits=b)
                 for b in controls}
        checks["control_stats"] = {"value": stats, "limit": None}
        g = stats[controls[0]]
    for k in ("widest_gap", "mean_gap"):
        if k in limits:
            checks[k] = {"value": g[k], "limit": limits[k]}
            ok = ok and g[k] <= limits[k]
    return {"correct": ok, "checks": checks}
