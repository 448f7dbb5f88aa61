"""Fixed sets of sizes and gaps, drawn the same for every seed.

A seed that changed the *set* of lengths would change the work of a run,
and runs with different seeds would differ for that reason alone. So a
distribution is sampled at evenly spaced quantiles ((i + 0.5) / n), which
gives every seed the same multiset, and the seed only permutes it.
"""

from __future__ import annotations

import math
import statistics
from typing import List

import numpy as np

_N = statistics.NormalDist()


def lognormal_lengths(n: int, median: float, sigma: float, lo: int, hi: int
                      ) -> List[int]:
    """n lengths at the (i + 0.5)/n quantiles of a lognormal with this
    median and sigma (of the log), clipped to [lo, hi]."""
    mu = math.log(median)
    return [int(min(hi, max(lo, round(math.exp(
        mu + sigma * _N.inv_cdf((i + 0.5) / n)))))) for i in range(n)]


def exponential_gaps(n: int, total: float) -> List[float]:
    """n inter-arrival gaps at the quantiles of an exponential, scaled to
    sum to `total`: Poisson-shaped arrivals, the same count every seed."""
    raw = [-math.log(1.0 - (i + 0.5) / n) for i in range(n)]
    scale = total / sum(raw)
    return [g * scale for g in raw]


def shuffled(values, rng: np.random.Generator) -> list:
    values = list(values)
    return [values[i] for i in rng.permutation(len(values))]
