"""Order statistics of the benchmark's samples."""
from __future__ import annotations

import math
import statistics


def nearest_rank(samples, q: float) -> float:
    """The q-th percentile by nearest rank: the smallest sample with at
    least q% of the samples at or below it. A tail is an observed sample,
    never an interpolation between the two largest."""
    a = sorted(float(s) for s in samples)
    if not a:
        raise ValueError("percentile of an empty sample set")
    if not 0.0 <= q <= 100.0:
        raise ValueError(f"q must be in [0, 100]; got {q}")
    return a[max(int(math.ceil(q / 100.0 * len(a))) - 1, 0)]


def spread(values) -> float:
    """Distance between the first and third quartile as a share of the
    median (``statistics.quantiles(values, n=4)``, its default method)."""
    q1, med, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / med
