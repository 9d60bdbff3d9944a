"""Markov-modulated Poisson arrivals (bursts):
``{"kind": "mmpp", "states": [{"rate_per_s": r, "mean_dwell_s": d}, ...]}``.

The chain cycles through the states in the order given, each visit lasting
an independent exponential dwell with the state's mean; it starts in its
stationary law (a state drawn with odds in proportion to its mean dwell).
Given that path, the window of T seconds holds n = round(mean rate * T)
arrivals at n independent times whose density follows the rate of the
state they fall in, sorted: the MMPP conditioned on its path and count.
"""
from __future__ import annotations

import numpy as np


def mean_rate(params: dict) -> float:
    states = params["states"]
    busy = sum(s["rate_per_s"] * s["mean_dwell_s"] for s in states)
    return busy / sum(s["mean_dwell_s"] for s in states)


def path(params: dict, seconds: float, rng: np.random.Generator):
    """(edges, rates): the chain holds rates[k] on [edges[k], edges[k+1]),
    from 0 to at least ``seconds``."""
    states = params["states"]
    dwell = np.asarray([s["mean_dwell_s"] for s in states], np.float64)
    k = int(rng.choice(len(states), p=dwell / dwell.sum()))
    edges, rates = [0.0], []
    while edges[-1] < seconds:
        rates.append(states[k]["rate_per_s"])
        edges.append(edges[-1] + rng.exponential(dwell[k]))
        k = (k + 1) % len(states)
    return np.asarray(edges), np.asarray(rates, np.float64)


def arrivals(params: dict, n: int, seconds: float,
             rng: np.random.Generator) -> np.ndarray:
    edges, rates = path(params, seconds, rng)
    lo, hi = edges[:-1], np.minimum(edges[1:], seconds)
    mass = np.concatenate([[0.0], np.cumsum(rates * (hi - lo))])
    u = np.sort(rng.uniform(0.0, mass[-1], n))
    k = np.clip(np.searchsorted(mass, u, side="right") - 1, 0, len(rates) - 1)
    return lo[k] + (u - mass[k]) / rates[k]
