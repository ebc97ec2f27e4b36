"""The benchmark's own arithmetic on a window of requests."""

from __future__ import annotations

import math


def percentile(values, p: float) -> float:
    """The p-th percentile (0..100) by linear interpolation between the two
    closest ranks, over ALL the values given (no trimming)."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of no values")
    rank = (len(xs) - 1) * p / 100.0
    lo, hi = math.floor(rank), math.ceil(rank)
    return xs[lo] + (xs[hi] - xs[lo]) * (rank - lo)


def rate(done_times, t_open: float, seconds: float) -> float:
    """Completions inside [t_open, t_open + seconds] over the whole of
    `seconds` — all the work over all the time, a stall included."""
    n = sum(1 for t in done_times if t_open <= t <= t_open + seconds)
    return n / seconds
