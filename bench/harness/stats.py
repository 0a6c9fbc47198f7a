"""Arithmetic of the end-to-end metrics, kept with the benchmark."""
from __future__ import annotations

import math
import statistics


def percentile(values, q: float) -> float:
    """Nearest-rank ``q``-th percentile (0 < q <= 100) over every value.

    A failed request is passed as ``math.inf`` and so counts as later
    than any answered one.
    """
    if not values:
        raise ValueError("percentile of no values")
    ordered = sorted(values)
    rank = max(1, math.ceil(q / 100.0 * len(ordered)))
    return ordered[rank - 1]


def rate(units: float, seconds: float) -> float:
    if seconds <= 0:
        raise ValueError("rate over an empty window")
    return units / seconds


def spread(values) -> float:
    """Interquartile distance as a share of the median."""
    q1, med, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / med
