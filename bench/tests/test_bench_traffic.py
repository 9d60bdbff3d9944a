"""The traffic generators: deterministic per seed, with the stated medians,
clips and rates, and the same work for every seed."""
import json
from collections import Counter
from pathlib import Path

import numpy as np
import pytest

from bench import schedule
from bench.traffic import gen_lognormal, gen_mmpp, gen_poisson

MIXES = Path(__file__).resolve().parents[1] / "traffic"
BIG = 2 ** 33 + 7          # seeds run past 32 bits


def _mix(name):
    return json.loads((MIXES / f"{name}.json").read_text())


@pytest.mark.parametrize("mix", ["chat", "burst"])
def test_schedule_deterministic_per_seed(mix):
    a = schedule.build(_mix(mix), vocab=1000, seed=BIG, seconds=20)
    b = schedule.build(_mix(mix), vocab=1000, seed=BIG, seconds=20)
    c = schedule.build(_mix(mix), vocab=1000, seed=BIG + 1, seconds=20)
    assert np.array_equal(a.due, b.due)
    assert np.array_equal(a.max_new, b.max_new)
    assert all(np.array_equal(p, q) for p, q in zip(a.prompts, b.prompts))
    assert not np.array_equal(a.due, c.due)
    # another seed: the same work in another order
    assert len(a) == len(c)
    assert Counter(map(len, a.prompts)) == Counter(map(len, c.prompts))
    assert Counter(a.max_new.tolist()) == Counter(c.max_new.tolist())
    assert np.all(np.diff(a.due) >= 0) and 0 <= a.due[0] and a.due[-1] < 20
    assert all(p.dtype == np.int32 and p.min() >= 0 and p.max() < 1000
               for p in a.prompts)


@pytest.mark.parametrize("spec", [
    {"median": 192, "sigma": 1.0, "min": 16, "max": 1024},
    {"median": 96, "sigma": 0.7, "min": 16, "max": 256},
    {"median": 48, "sigma": 1.0, "min": 8, "max": 256},
    {"median": 24, "sigma": 0.7, "min": 4, "max": 64},
])
def test_lognormal_median_and_clips(spec):
    x = gen_lognormal.lengths(spec, 1001, np.random.default_rng(1))
    assert np.median(x) == spec["median"]
    assert x.min() == spec["min"] and x.max() == spec["max"]
    z = np.log(x[(x > spec["min"]) & (x < spec["max"])])
    assert np.std(z) == pytest.approx(spec["sigma"], rel=0.35)


def test_poisson_count_and_gaps():
    n, T = 500, 100.0
    t = gen_poisson.arrivals({"rate_per_s": 5.0}, n, T,
                             np.random.default_rng(3))
    assert len(t) == n and np.all(np.diff(t) >= 0) and 0 <= t[0] and t[-1] < T
    gaps = np.diff(t)
    assert np.mean(gaps) == pytest.approx(0.2, rel=0.05)
    # exponential gaps: as many below the mean's ln 2 as above it
    assert np.mean(gaps < 0.2 * np.log(2)) == pytest.approx(0.5, abs=0.05)
    assert schedule.build(dict(_mix("chat"), arrivals={
        "kind": "poisson", "rate_per_s": 5.0}), vocab=10, seed=1,
        seconds=T).due.shape == (n,)


def test_poisson_keeps_its_clumps():
    """Eight consecutive gaps sum like Gamma(8), whose spread is 1/sqrt(8)
    of its mean: the arrivals are not evened out."""
    t = gen_poisson.arrivals({"rate_per_s": 1.0}, 8001, 8001.0,
                             np.random.default_rng(11))
    sums = np.diff(t[::8])
    assert np.std(sums) / np.mean(sums) == pytest.approx(8 ** -0.5, rel=0.1)


def test_mmpp_rate_and_bursts():
    spec = _mix("burst")["arrivals"]
    hi, lo = (s["rate_per_s"] for s in spec["states"])
    assert gen_mmpp.mean_rate(spec) == pytest.approx((hi * 1 + lo * 4) / 5)
    T = 600.0
    n = int(round(gen_mmpp.mean_rate(spec) * T))
    rng = np.random.default_rng(5)
    t = gen_mmpp.arrivals(spec, n, T, rng)
    assert len(t) == n and np.all(np.diff(t) >= 0) and 0 <= t[0] and t[-1] < T
    # per-second counts: the busiest seconds run near the burst rate, the
    # quietest near the quiet one
    counts = np.bincount(t.astype(int), minlength=int(T))
    assert np.percentile(counts, 95) > 0.6 * hi
    assert np.percentile(counts, 25) < 2 * lo
