"""Percentiles and spreads, as the benchmark reports them. Stdlib only."""

from __future__ import annotations

import math
import statistics
from typing import Sequence, Tuple


def percentile(values: Sequence[float], q: float) -> Tuple[float, int]:
    """Nearest-rank percentile: the smallest value with at least q % of
    the sample at or below it. Returns (value, sample count); raises on an
    empty sample, because a percentile of nothing is not 0."""
    if not values:
        raise ValueError("percentile of an empty sample")
    if not 0 < q <= 100:
        raise ValueError("q must be in (0, 100]")
    vals = sorted(values)
    rank = max(1, math.ceil(q / 100.0 * len(vals)))
    return vals[rank - 1], len(vals)


def iqr_share(values: Sequence[float]) -> float:
    """(Q3 - Q1) / median with Python's statistics.quantiles(n=4): the
    spread the bounds in BENCHMARK.json are set from."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)
