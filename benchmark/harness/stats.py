"""The arithmetic of the end-to-end metrics and of a spread."""

from __future__ import annotations

import math
import statistics


def percentile(values, q: float) -> float:
    """The ``q``-th percentile (0..100) of ALL ``values``, nearest rank:
    the smallest value with at least q % of the sample at or below it. A
    tail is the tail of every segment, never of a trimmed sample."""
    if not values:
        raise ValueError("percentile of no values")
    ordered = sorted(values)
    rank = max(1, math.ceil(q / 100.0 * len(ordered)))
    return ordered[rank - 1]


def median(values) -> float:
    return statistics.median(values)


def rate(work: float, seconds: float) -> float:
    """All the work of the window over all of its wall seconds."""
    if seconds <= 0:
        raise ValueError(f"a window of {seconds} s")
    return work / seconds


def spread(values) -> float:
    """Distance between the first and third quartile as a share of the
    median, with the quartiles ``statistics.quantiles(n=4)`` gives (the
    driver's rule for a bound)."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)
