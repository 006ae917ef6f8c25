"""Window statistics over every sample: no medians of chunks."""
from __future__ import annotations

import numpy as np


def percentile(values, q: float) -> float:
    """The ``q``-th percentile of all ``values`` (linear interpolation
    between order statistics); NaN when there are none."""
    v = np.asarray(list(values), np.float64)
    return float(np.percentile(v, q)) if v.size else float("nan")


class Window:
    """Requests and ticks seen from one measured window ``[w0, w1]``."""

    def __init__(self, w0, w1, reqs, ticks, lag=()):
        self.w0, self.w1 = w0, w1
        self.seconds = w1 - w0
        self.reqs = list(reqs)
        self.ticks = list(ticks)
        self.lag = list(lag)
        self.due = [r for r in self.reqs if w0 <= r.due < w1]
        self.tokens = sum(1 for r in self.reqs for t in r.times
                          if w0 < t <= w1)

    def ttft(self) -> list:
        """From when each request was due to the end of the tick that made
        its first token; a request still waiting at the close enters with
        its wait so far."""
        out = []
        for r in self.due:
            first = r.times[0] if r.times and r.times[0] <= self.w1 \
                else self.w1
            out.append(first - r.due)
        return out

    def itl(self) -> list:
        """Every gap between consecutive tokens of one request that ends
        inside the window."""
        out = []
        for r in self.reqs:
            t = r.times
            out.extend(b - a for a, b in zip(t, t[1:])
                       if self.w0 < b <= self.w1)
        return out
