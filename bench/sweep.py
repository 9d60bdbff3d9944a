#!/usr/bin/env python3
"""Find a cell's knee once, on the chip: the highest steady Poisson rate.

  python3 bench/sweep.py --workload qwen3-4b.chat --rates 1,2,3,4 --seconds 20

One process builds the cell's engine and weights once, warms every shape,
then offers each rate in turn as Poisson arrivals with the cell's length
distributions, each for ``--seconds`` and its drain.  Per rate it prints
one JSON line: the requests due and finished, the backlog (submitted and
not finished) at a third, two thirds and the end of the window, and the
tails.  A rate is steady when the backlog does not grow across the window.
The benchmark's own runs never call this; the rate it finds is written
into the cell's traffic file by hand, with the sweep in PERF.md.
"""
import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def backlog(reqs, steps, t):
    t1 = steps.t1
    done = sum(1 for r in reqs if r.done and t1[r.token_steps[-1]] <= t)
    return sum(1 for r in reqs if r.submit is not None and r.submit <= t) - done


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT)]
    from bench.run import start_jax
    if start_jax(1) is None:
        return 2
    import jax
    from bench import cell as cell_mod
    from bench import harness, model, schedule, stats
    from repro.serving.engine import Request, ServingEngine

    cell = cell_mod.load(args.workload)
    cfg, mix = cell["config"], cell["traffic"]
    rates = [float(r) for r in args.rates.split(",")]
    w = model.init_weights(cfg, jax.random.key(args.seed))
    engine = ServingEngine(model.arch_config(cfg), model.engine_params(w),
                           slots=mix["slots"], s_max=mix["s_max"])
    scheds = {r: schedule.build(
        dict(mix, arrivals={"kind": "poisson", "rate_per_s": r}),
        vocab=cfg["vocab_size"], seed=args.seed, seconds=args.seconds)
        for r in rates}
    for s in scheds.values():
        harness._warm_up(engine, s, Request)
    for r in rates:
        t = time.perf_counter()
        reqs, steps, end, _ = harness.drive(engine, scheds[r], args.seconds,
                                            Request)
        rec = {"requests": reqs, "steps": steps, "end_s": end,
               "seconds": args.seconds}
        T = args.seconds
        print(json.dumps({
            "rate": r, "due": len(reqs), "finished": sum(r.done for r in reqs),
            "backlog": [backlog(reqs, steps, T * f) for f in (1 / 3, 2 / 3, 1)],
            "ttft_p50": stats.tail(stats.ttft(rec), 50),
            "ttft_p90": stats.tail(stats.ttft(rec), 90),
            "itl_p95": stats.tail(stats.itl(rec), 95),
            "itl_p99": stats.tail(stats.itl(rec), 99),
            "steps": len(steps.t0), "drain_end_s": end,
            "wall_s": time.perf_counter() - t}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
