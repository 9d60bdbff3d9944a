"""Lognormal lengths, clipped:
``{"kind": "lognormal", "median": m, "sigma": s, "min": a, "max": b}``.

The n lengths are the distribution's quantiles at (i + 0.5) / n, rounded
and clipped, in a uniformly random order from the seed.  Every seed then
asks for the same total work, and any stretch of requests is a draw
without replacement from the distribution.
"""
from __future__ import annotations

from statistics import NormalDist

import numpy as np


def lengths(params: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    z = np.asarray([NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
    x = np.exp(np.log(params["median"]) + params["sigma"] * z)
    x = np.clip(np.rint(x), params["min"], params["max"]).astype(np.int32)
    return rng.permutation(x)
