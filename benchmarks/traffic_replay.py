"""Traffic-replay benchmark: the serving->trace->MEC loop, measured.

  PYTHONPATH=src python -m benchmarks.traffic_replay --cells 16

Pipeline (the tentpole demo of the traffic subsystem):

1. **Record** -- drive a small ServingEngine under a bursty submission
   schedule with a ``TrafficRecorder`` attached; bin the submit events into
   the canonical slot-indexed (T, N) arrival trace (``--source mmpp`` skips
   the engine and materializes an MMPP process instead -- faster, pure-MEC).
2. **Replay** -- build B ``trace_replay`` cells (each a de-phased rotation
   of the trace) and evaluate them two ways over the same slots:

   * batched -- ``ScenarioGrid.make_rollout``: one jitted vmap+scan program;
   * loop    -- one jitted single-cell episode re-dispatched per cell, with
     the grid's own fold_in key discipline so both legs draw identical
     randomness.

3. **Check + measure** -- per-cell mean rewards must agree to 1e-5
   (batched==looped parity), then slots/sec and the batched-over-loop
   speedup are reported.  CSV rows follow the benchmarks/run.py convention.

``--devices N`` adds a cells-sharded replay leg over N forced host devices,
and ``--model M`` makes it the 2-D ``("cells", "model")`` mesh (N/M cell
shards x M-way per-cell tensor parallelism); layout preconditions are
validated up front, as in benchmarks/scenario_grid.py.

``--gate 0`` (default) is informational; pass a positive speedup bar to get
a nonzero exit code below it (CI runs the informational mode -- the hard 5x
bar lives in benchmarks/scenario_grid.py where the grid is larger).
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np


def _sync(tree):
    jax.block_until_ready(tree)


def record_serving_trace(n_ue: int, ticks: int = 60, seed: int = 0,
                         engine: str = "continuous"):
    """Drive a tiny ServingEngine under a bursty schedule; bin the submits.

    ``engine`` picks the serving mode: ``"continuous"`` (default -- per-tick
    admission over the paged KV pool) or ``"sync"`` (the synchronized-batch
    compat mode; benchmarks/serving_latency.py A/Bs the two head-to-head).
    """
    from repro import traffic
    from repro.configs.base import get_config, reduced
    from repro.models import transformer
    from repro.serving.engine import Request, ServingEngine

    cfg = reduced(get_config("qwen3-0.6b"), n_layers=4)
    params = transformer.init_params(jax.random.PRNGKey(seed), cfg)
    rec = traffic.TrafficRecorder()
    eng = ServingEngine(cfg, params, slots=2, s_max=32, recorder=rec,
                        sync_batching=(engine == "sync"))

    rng = np.random.default_rng(seed)
    rid = 0
    for tick in range(ticks):
        # bursty: quiet baseline with a 3x surge in the middle third
        lam = 0.9 if ticks // 3 <= tick < 2 * ticks // 3 else 0.3
        for _ in range(rng.poisson(lam)):
            eng.submit(Request(rid=rid,
                               prompt=rng.integers(0, cfg.vocab, 6)
                               .astype(np.int32),
                               max_new=2, ue=rid % n_ue))
            rid += 1
        eng.step()
    eng.run_until_idle()
    trace = rec.to_trace(n_ue=n_ue, bin_ticks=2, slot_s=1.0,
                         horizon=ticks // 2)
    lat = rec.latency_stats()
    print(f"recorded {rid} requests over {eng.clock} engine ticks "
          f"({engine} engine, p50/p99 E2E "
          f"{lat.get('p50', 0):.0f}/{lat.get('p99', 0):.0f} ticks) -> "
          f"trace T={trace.n_slots} x N={trace.n_ue}, "
          f"mean {trace.rates.mean():.2f} req/s, "
          f"peak {trace.rates.max():.2f} req/s")
    return trace


def mmpp_trace(n_ue: int, horizon: int = 200, seed: int = 0):
    from repro import traffic
    proc = traffic.make_mmpp(n_ue, seed=seed, rates=(0.5, 3.0),
                             horizon=horizon)
    return traffic.from_process(proc, horizon)


def build_grid(trace, cells: int, seed: int):
    from repro.core.scenarios import ScenarioGrid, make
    stride = max(1, trace.n_slots // cells)
    return ScenarioGrid([make("trace_replay", trace=trace,
                              offset=b * stride, seed=seed + b)
                         for b in range(cells)])


def bench_batched(grid, policy: str, steps: int, repeats: int):
    fn = grid.make_rollout(policy, steps)
    key = jax.random.PRNGKey(0)
    _, _, summary = jax.block_until_ready(fn(key))        # compile
    _sync(fn(key))                                        # reprolint: ignore[key-reuse] (warm: same program on purpose)
    best = float("inf")
    for r in range(repeats):
        t0 = time.perf_counter()
        _sync(fn(jax.random.fold_in(key, r)))
        best = min(best, time.perf_counter() - t0)
    return best, grid.b * steps / best, summary


def bench_loop(grid, policy: str, steps: int, repeats: int):
    """Per-cell loop with the SAME randomness as the batched rollout: reset
    keys come from gridshard.cell_keys(k0, b), exactly as grid.reset does."""
    from repro.core import gridshard, sweep
    from repro.core.env import reset_p, step_p
    from repro.core.scenarios import POLICIES

    oracle = policy == "oracle"
    act = None if oracle else POLICIES[policy]

    @jax.jit
    def episode(params, k0):
        st0 = reset_p(params, k0)

        def body(carry, _):
            st, k = carry
            k, k_act = jax.random.split(k)
            cut = (sweep.oracle_cut_p(params, st) if oracle
                   else act(params, st, k_act))
            st2, res = step_p(params, st, cut)
            return (st2, k), res.reward
        (_, _), rewards = jax.lax.scan(body, (st0, k0), None, length=steps)
        return rewards

    cell_params = [jax.tree.map(lambda x, b=b: x[b], grid.params)
                   for b in range(grid.b)]
    key, k0 = jax.random.split(jax.random.PRNGKey(0))
    cell_keys = gridshard.cell_keys(k0, grid.b)
    _sync(episode(cell_params[0], cell_keys[0]))          # compile
    _sync(episode(cell_params[0], cell_keys[0]))          # warm
    rewards = [np.asarray(episode(p, k))
               for p, k in zip(cell_params, cell_keys)]
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        for p, k in zip(cell_params, cell_keys):
            _sync(episode(p, k))
        best = min(best, time.perf_counter() - t0)
    return best, grid.b * steps / best, np.stack(rewards)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--cells", type=int, default=16)
    ap.add_argument("--ues", type=int, default=4)
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--policy", default="oracle",
                    choices=("oracle", "local", "edge"))
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--source", default="serving",
                    choices=("serving", "mmpp"),
                    help="record the trace from a live ServingEngine run "
                         "(the full loop) or materialize an MMPP process")
    ap.add_argument("--engine", default="continuous",
                    choices=("continuous", "sync"),
                    help="serving mode for --source serving: continuous "
                         "batching (paged KV) or the synchronized-batch "
                         "compat mode")
    ap.add_argument("--save-trace", default=None, metavar="NPZ",
                    help="also save the recorded trace for reuse "
                         "(python -m repro.traffic --show NPZ)")
    ap.add_argument("--devices", type=int, default=0,
                    help="also run a cells-sharded replay leg over this "
                         "many (forced host) devices")
    ap.add_argument("--model", type=int, default=1,
                    help="per-cell tensor-parallel degree for the sharded "
                         "leg (('cells','model') mesh; must divide "
                         "--devices)")
    ap.add_argument("--gate", type=float, default=0.0,
                    help="min batched-over-loop speedup for exit code 0 "
                         "(0 = informational)")
    args = ap.parse_args(argv)

    from benchmarks._sharded import (backend_ready, force_devices, leg_tag,
                                     validate_mesh_args)
    err = validate_mesh_args(args.devices, args.model)
    if err:
        print(f"error: {err}")
        return 2
    if args.devices:
        force_devices(args.devices)   # before jax initializes its backend

    trace = (record_serving_trace(args.ues, seed=args.seed,
                                  engine=args.engine)
             if args.source == "serving"
             else mmpp_trace(args.ues, seed=args.seed))
    if args.save_trace:
        trace.save(args.save_trace)
        print(f"trace saved to {args.save_trace}")

    grid = build_grid(trace, args.cells, args.seed)
    print(f"replay grid: B={grid.b} cells x N={grid.n_ue} UEs, "
          f"{args.steps} slots, policy={args.policy}, "
          f"backend={jax.default_backend()}")

    print("name,us_per_call,derived")
    dt_b, sps_b, summary = bench_batched(grid, args.policy, args.steps,
                                         args.repeats)
    print(f"traffic_replay_batched[{grid.b}x{grid.n_ue}],{dt_b*1e6:.0f},"
          f"slots_per_s={sps_b:.0f}")
    dt_l, sps_l, loop_rewards = bench_loop(grid, args.policy, args.steps,
                                           args.repeats)
    print(f"traffic_replay_loop[{grid.b}x{grid.n_ue}],{dt_l*1e6:.0f},"
          f"slots_per_s={sps_l:.0f}")

    if args.devices:
        tag = leg_tag(args.devices, args.model)
        if not backend_ready(args.devices):
            print(f"error: the sharded leg asked for {args.devices} "
                  f"devices but only {len(jax.devices())} are live (the "
                  "backend initialized before the device flag landed)")
            return 1
        from repro.launch.mesh import make_cells_mesh
        grid_sh = build_grid(trace, args.cells, args.seed)
        grid_sh.use_mesh(make_cells_mesh(args.devices,
                                         model=args.model))
        dt_s, sps_s, sum_s = bench_batched(grid_sh, args.policy,
                                           args.steps, args.repeats)
        err_s = float(np.max(np.abs(
            np.asarray(sum_s["reward"]) - np.asarray(summary["reward"]))
            / np.maximum(np.abs(np.asarray(summary["reward"])), 1e-7)))
        print(f"traffic_replay_sharded[{grid.b}x{grid.n_ue}{tag}],"
              f"{dt_s*1e6:.0f},slots_per_s={sps_s:.0f}")
        print(f"traffic_replay_sharded_parity[{grid.b}x{grid.n_ue}"
              f"{tag}],0,max_rel_err={err_s:.2e}"
              f"_{'OK' if err_s < 1e-5 else 'FAIL'}")
        if err_s >= 1e-5:
            print("PARITY FAILURE: sharded and batched replays diverged")
            return 1

    # batched == looped parity on per-cell mean reward (identical keys)
    batched = np.asarray(summary["reward"])
    looped = loop_rewards.mean(axis=1)
    err = float(np.max(np.abs(batched - looped)
                       / np.maximum(np.abs(looped), 1e-7)))
    ok_parity = err < 1e-5
    print(f"traffic_replay_parity[{grid.b}x{grid.n_ue}],0,"
          f"max_rel_err={err:.2e}_{'OK' if ok_parity else 'FAIL'}")

    speedup = sps_b / sps_l
    print(f"traffic_replay_speedup[{grid.b}x{grid.n_ue}],0,"
          f"batched_over_loop={speedup:.1f}x")
    if not ok_parity:
        print("PARITY FAILURE: batched and looped rollouts diverged")
        return 1
    if args.gate <= 0:
        print(f"speedup: {speedup:.1f}x (gate disabled)")
        return 0
    ok = speedup >= args.gate
    print(f"speedup: {speedup:.1f}x "
          f"({'meets' if ok else 'BELOW'} the {args.gate:g}x bar)")
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
