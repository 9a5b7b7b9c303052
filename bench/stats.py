"""Exact order statistics over per-request records."""
from __future__ import annotations

import math
import statistics


def percentile(values, q: float) -> float:
    """Nearest-rank percentile: the ceil(q/100 * n)-th smallest value.
    A failed or unfinished request enters as ``math.inf``, so it misses
    every limit; an empty sample gives ``nan``."""
    vals = sorted(values)
    if not vals:
        return math.nan
    rank = max(1, math.ceil(q / 100.0 * len(vals)))
    return vals[rank - 1]


def median(values) -> float:
    vals = list(values)
    return statistics.median(vals) if vals else math.nan


def spread(values) -> float:
    """(Q3 - Q1) / median, by ``statistics.quantiles(n=4)``."""
    q1, q2, q3 = statistics.quantiles(list(values), n=4)
    return (q3 - q1) / q2
