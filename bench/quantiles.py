"""Exact percentiles of the client's own samples, and the spread of a
metric over runs."""

from __future__ import annotations

import math
import statistics


def percentile(values, p: float) -> float:
    """The nearest-rank ``p``-th percentile: the smallest sample with at
    least ``p`` percent of the samples at or below it.  Always one of the
    samples, never an interpolation or a histogram bucket's bound."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of no samples")
    if not 0 < p <= 100:
        raise ValueError(f"p={p} outside (0, 100]")
    return xs[max(1, math.ceil(p / 100 * len(xs))) - 1]


def spread(values) -> float:
    """The distance between the first and third quartiles, as
    ``statistics.quantiles(values, n=4)`` gives them, over the median."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)
