"""Percentile, rate and spread arithmetic, kept with the benchmark so every
change computes them the same way."""
from __future__ import annotations

import math
import statistics
from typing import Optional, Sequence


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    """Nearest-rank percentile: the smallest value with at least `q` percent
    of the values at or below it.  `inf` entries (failed requests) sort last,
    so they count as infinitely late.  None for no values."""
    if not values:
        return None
    if not 0 < q <= 100:
        raise ValueError(f"percentile {q} outside (0, 100]")
    ordered = sorted(values)
    rank = math.ceil(q / 100 * len(ordered))
    return ordered[max(rank, 1) - 1]


def mean(values: Sequence[float]) -> Optional[float]:
    return sum(values) / len(values) if values else None


def per_item_ms(window_s: float, items: int) -> Optional[float]:
    """Milliseconds per item over a window: the time of all the work over
    all the items done in it."""
    if items <= 0:
        return None
    return window_s * 1e3 / items


def spread(values: Sequence[float]) -> float:
    """Interquartile distance as a share of the median, by
    `statistics.quantiles(values, n=4)` (the exclusive method)."""
    q1, q2, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / q2
