"""Traffic generator: one general generator driven by a mix's data file.

A mix (``bench/traffic/<name>.json``) names its loop and its length
distributions; nothing here knows a mix by name.  The structure of the
stream is the same for every seed: request ``i`` has the same prompt
length, output length, arrival gap and shared-prefix choice whatever the
seed, drawn at stratified quantiles of the mix's distributions and put in
one fixed order.  The seed draws only the token ids.  Runs with different
seeds then do the same work in the same order: the engine's scheduling
depends on lengths alone, so a closed loop admits and finishes the same
requests at the same ticks.

Loops:

* ``closed``  -- ``clients`` callers, each sending its next request when the
  previous one has finished;
* ``poisson`` -- an open loop with exponential gaps at ``rate_per_s``;
* ``burst``   -- an open loop whose rate follows a cycle of phases
  ``[[seconds, rate_per_s], ...]``, starting at the first phase.
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import List, Optional

import numpy as np

LOOPS = ("closed", "poisson", "burst")
POOL = 512                      # stratified draws per length distribution
ORDER = 0                       # the one fixed order of the stratified draws
STAGGER = 4                     # first-request lengths of a closed loop


@dataclasses.dataclass(frozen=True)
class Req:
    """One request of the schedule: prompt ids and output length.  ``due``
    is its arrival time in seconds from the start of the open-loop schedule
    (None in a closed loop, where the client's clock decides)."""
    prompt: np.ndarray
    max_tokens: int
    due: Optional[float] = None


def quantile(dist: dict, u: float) -> int:
    """Length at quantile ``u`` of a ``{"dist": "lognormal", "median",
    "sigma", "min", "max"}`` or ``{"dist": "fixed", "value"}`` spec."""
    if dist["dist"] == "fixed":
        return int(dist["value"])
    if dist["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    z = NormalDist().inv_cdf(u)
    v = int(round(dist["median"] * math.exp(dist["sigma"] * z)))
    return int(min(max(v, dist["min"]), dist["max"]))


def stratified(dist: dict, n: int = POOL) -> np.ndarray:
    """``n`` lengths at the quantiles (i + 0.5) / n: the same set for
    every seed."""
    return np.array([quantile(dist, (i + 0.5) / n) for i in range(n)],
                    np.int64)


def length_bounds(dist: dict) -> tuple:
    if dist["dist"] == "fixed":
        return int(dist["value"]), int(dist["value"])
    return int(dist["min"]), int(dist["max"])


class Generator:
    """Requests of one mix for one seed, in order.

    ``next()`` returns the next request of the stream; an open loop's
    requests carry their ``due`` time.  A closed loop's first requests
    take ``STAGGER`` output lengths spread between the shortest and the
    lower quartile: the slots fall out of step within a short pre-roll,
    which pays few admissions (each one is a full-width launch).
    """

    def __init__(self, mix: dict, seed: int, vocab: int):
        if mix["loop"] not in LOOPS:
            raise ValueError(f"unknown loop {mix['loop']!r}; one of {LOOPS}")
        self.mix, self.vocab = mix, int(vocab)
        self.rng = np.random.default_rng(int(seed))     # token ids only
        fixed = np.random.default_rng(ORDER)
        self.prompts = stratified(mix["prompt_len"])[fixed.permutation(POOL)]
        self.outputs = stratified(mix["output_len"])[fixed.permutation(POOL)]
        u = (np.arange(POOL) + 0.5) / POOL
        self._gaps = -np.log1p(-u)[fixed.permutation(POOL)]  # unit rate
        shared = mix.get("shared_prefix")
        if shared:
            self.prefixes = self.rng.integers(
                0, self.vocab, (shared["count"], shared["len"]))
            self._shares = fixed.uniform(size=POOL) < shared["share"]
            self._which = fixed.integers(0, shared["count"], POOL)
        self._i = 0
        self._t = 0.0
        if mix["loop"] == "burst":
            self._cycle = float(sum(p[0] for p in mix["phases"]))
            self._phase = 0.0
        self._first = None
        if mix["loop"] == "closed":
            lo = length_bounds(mix["output_len"])[0]
            q1 = quantile(mix["output_len"], 0.25)
            levels = [int(round(lo + (i + 0.5) / STAGGER * (q1 - lo)))
                      for i in range(STAGGER)]
            n = int(mix["clients"])
            self._first = [levels[i * STAGGER // n] for i in range(n)]

    def _advance(self, t: float, e: float) -> float:
        """The time at which ``e`` units of cumulative rate have passed
        since ``t``: exact for a piecewise-constant rate (time rescaling
        of a unit-rate Poisson process)."""
        if self.mix["loop"] == "poisson":
            return t + e / float(self.mix["rate_per_s"])
        while True:
            s = (t + self._phase) % self._cycle
            for dur, rate in self.mix["phases"]:
                if s < dur:
                    left = dur - s
                    break
                s -= dur
            if e <= rate * left:
                return t + e / rate
            e -= rate * left
            t += left

    def _prompt(self, L: int, j: int) -> np.ndarray:
        shared = self.mix.get("shared_prefix")
        if shared and self._shares[j]:
            # a shared system prompt, then at least ``min_suffix`` own
            # tokens: no prompt is a prefix of another
            L = max(L, shared["len"] + shared["min_suffix"])
        toks = self.rng.integers(0, self.vocab, L)
        if shared and self._shares[j]:
            toks[:shared["len"]] = self.prefixes[self._which[j]]
        return toks.astype(np.int32)

    def next(self) -> Req:
        j = self._i % POOL
        L, G = int(self.prompts[j]), int(self.outputs[j])
        if self._first is not None and self._i < len(self._first):
            G = self._first[self._i]
        due = None
        if self.mix["loop"] != "closed":
            self._t = self._advance(self._t, float(self._gaps[j]))
            due = self._t
        self._i += 1
        return Req(self._prompt(L, j), G, due)

    def take(self, n: int) -> List[Req]:
        return [self.next() for _ in range(n)]
