"""Percentiles, one definition for every reader."""

from __future__ import annotations

import math
from typing import Sequence


def percentile(values: Sequence[float], q: float) -> float:
    """The q-th percentile (0..100) by linear interpolation between order
    statistics; raises on an empty sample, because a metric with nothing
    to read is left out, never reported as 0."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of an empty sample")
    k = (len(xs) - 1) * q / 100.0
    lo, hi = math.floor(k), math.ceil(k)
    return xs[lo] + (xs[hi] - xs[lo]) * (k - lo)


STATS = {
    "p50": lambda v: percentile(v, 50),
    "p90": lambda v: percentile(v, 90),
    "p95": lambda v: percentile(v, 95),
    "p99": lambda v: percentile(v, 99),
    "mean": lambda v: sum(v) / len(v),
    "max": max,
    "sum": sum,
}
