"""Percentiles and spreads, in one place and in plain Python."""

from __future__ import annotations

import math
import statistics
from typing import Iterable, List, Optional


def percentile(values: Iterable[float], q: float) -> Optional[float]:
    """Nearest-rank percentile: the smallest value with at least ``q`` of
    the sample at or below it. None for an empty sample. At q = 0.95 a
    sample of 200 leaves ten values beyond the one returned."""
    ordered = sorted(values)
    if not ordered:
        return None
    rank = max(1, math.ceil(q * len(ordered)))
    return ordered[rank - 1]


def median(values: Iterable[float]) -> Optional[float]:
    ordered = sorted(values)
    return statistics.median(ordered) if ordered else None


def spread(values: List[float]) -> float:
    """Distance between the first and third quartile as a share of the
    median, with the quartiles of ``statistics.quantiles(values, n=4)``:
    the spread the bounds of BENCHMARK.json are set from."""
    q1, _q2, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)
