"""Percentiles and spreads, kept with the benchmark so that every PR
computes them the same way."""

from __future__ import annotations

import math
import statistics
from typing import Sequence


def percentile(values: Sequence[float], q: float) -> float:
    """The q-th percentile with linear interpolation between the closest
    ranks (numpy's default method, which the engine's own stats use)."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of an empty sample")
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def quartile_spread(values: Sequence[float]) -> float:
    """Distance between the first and third quartile as a share of the
    median, with the quartiles ``statistics.quantiles(values, n=4)``
    gives."""
    q1, med, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / med
