"""Poisson arrivals: ``{"kind": "poisson", "rate_per_s": r}``.

The window of T seconds holds n = round(r * T) arrivals at n independent
uniform times on [0, T), sorted: a Poisson process conditioned on its
count.  Every seed offers the same number of requests; the clumps and
lulls between them are those of Poisson traffic.
"""
from __future__ import annotations

import numpy as np


def mean_rate(params: dict) -> float:
    return float(params["rate_per_s"])


def arrivals(params: dict, n: int, seconds: float,
             rng: np.random.Generator) -> np.ndarray:
    return np.sort(rng.uniform(0.0, seconds, n))
