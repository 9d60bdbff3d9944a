"""Batched scenario-grid benchmark: measures the speedup of evaluating B
cells x N UEs in ONE jitted vmap+scan program over the equivalent per-cell
Python loop, so the batching win is measured, not claimed.

  PYTHONPATH=src python -m benchmarks.scenario_grid --cells 64 --ues 8

All legs run the identical per-cell math (reset + `steps` slots of policy
decision -> C7 projection -> P3/P4/P5 convex allocation -> queue update):

* batched  -- ``ScenarioGrid.make_rollout``: vmap over cells inside one
  ``lax.scan`` over slots; a single dispatch for the whole grid.
* loop     -- one jitted single-cell episode (same scan over slots),
  compiled once and re-dispatched from Python per cell.
* sharded  -- (``--devices N``) the batched grid placed over an N-way
  ``("cells",)`` mesh (``ScenarioGrid.use_mesh``); on CPU the devices are
  forced with ``XLA_FLAGS=--xla_force_host_platform_device_count=N``, which
  this script sets itself BEFORE jax initializes -- so ``--devices`` only
  works when nothing else touched the backend first (always true under
  ``python -m benchmarks.scenario_grid``).  Forced host devices share the
  machine's cores, so the sharded leg measures partitioning overhead /
  scaling shape, not a real multi-chip speedup; it is reported, not gated.
* 2-D sharded -- (``--model M``, with ``--devices N``) the same grid over
  the ``("cells", "model")`` mesh: N/M cell shards x M-way per-cell tensor
  parallelism (``use_mesh(model=M)``).  Layout preconditions (M divides N,
  N devices actually forcible) are validated up front with actionable
  errors -- never an opaque XLA device-assignment failure.

Reported unit: slots/sec, where one slot = one (cell, time-slot) advance of
all N UEs.  CSV rows follow the benchmarks/run.py convention.
"""
from __future__ import annotations

import argparse
import time

import jax


def _sync(tree):
    jax.block_until_ready(tree)


def build_grid(cells: int, ues: int, seed: int):
    from repro.core.scenarios import ScenarioGrid, multicell_grid
    return ScenarioGrid(multicell_grid(cells=cells, ues=ues, seed=seed))


def bench_batched(grid, policy: str, steps: int, repeats: int):
    fn = grid.make_rollout(policy, steps)
    key = jax.random.PRNGKey(0)
    _sync(fn(key))                       # compile
    _sync(fn(key))                       # reprolint: ignore[key-reuse] (warm: same program on purpose)
    best = float("inf")
    for r in range(repeats):             # min-of-N: robust to CPU co-tenancy
        t0 = time.perf_counter()
        _sync(fn(jax.random.fold_in(key, r)))
        best = min(best, time.perf_counter() - t0)
    return best, grid.b * steps / best


def bench_loop(grid, policy: str, steps: int, repeats: int):
    from repro.core.env import reset_p, step_p
    from repro.core.scenarios import POLICIES

    act = POLICIES[policy]

    @jax.jit
    def episode(params, key):
        key, k0 = jax.random.split(key)
        st0 = reset_p(params, k0)

        def body(carry, _):
            st, k = carry
            k, k_act = jax.random.split(k)
            st2, res = step_p(params, st, act(params, st, k_act))
            return (st2, k), res.reward

        (_, _), rewards = jax.lax.scan(body, (st0, key), None, length=steps)
        return rewards

    cell_params = [s.params() for s in grid.scenarios]
    key = jax.random.PRNGKey(0)
    _sync(episode(cell_params[0], key))  # compile once (shapes shared)
    _sync(episode(cell_params[0], key))  # reprolint: ignore[key-reuse] (warm: same program on purpose)
    best = float("inf")
    for r in range(repeats):             # min-of-N: robust to CPU co-tenancy
        t0 = time.perf_counter()
        for b, params in enumerate(cell_params):
            _sync(episode(params, jax.random.fold_in(key, r * grid.b + b)))
        best = min(best, time.perf_counter() - t0)
    return best, grid.b * steps / best


def bench_payload(*, cells: int = 8, ues: int = 4, steps: int = 24,
                  repeats: int = 2, policy: str = "oracle",
                  seed: int = 0) -> dict:
    """Small-grid batched-vs-loop measurement as a JSON-ready block for the
    ``BENCH_N.json`` perf-trajectory artifact (benchmarks/run.py).  Defaults
    are far below the CLI's gate-grade 64x8 run on purpose: the artifact
    tracks the speedup trend per PR, the CLI ``--gate`` proves it."""
    grid = build_grid(cells, ues, seed)
    sec_b, sps_b = bench_batched(grid, policy, steps, repeats)
    sec_l, sps_l = bench_loop(grid, policy, steps, repeats)
    return {
        "config": {"cells": cells, "ues": ues, "steps": steps,
                   "repeats": repeats, "policy": policy, "seed": seed},
        "batched": {"best_seconds": sec_b, "slots_per_s": round(sps_b, 1)},
        "loop": {"best_seconds": sec_l, "slots_per_s": round(sps_l, 1)},
        "batched_speedup": round(sps_b / sps_l, 3),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--cells", type=int, default=64)
    ap.add_argument("--ues", type=int, default=8)
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--policy", default="oracle",
                    choices=("oracle", "local", "edge", "random"))
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--devices", type=int, default=0,
                    help="also run a cells-sharded leg over this many "
                         "(forced host) devices")
    ap.add_argument("--model", type=int, default=1,
                    help="per-cell tensor-parallel degree for the sharded "
                         "leg: a ('cells','model') mesh with --devices/M "
                         "cell shards x M-way model parallelism "
                         "(requires --devices divisible by M)")
    ap.add_argument("--gate", type=float, default=5.0,
                    help="min batched-over-loop speedup for exit code 0 "
                         "(0 disables the gate -- e.g. informational runs "
                         "on small configs or contended runners)")
    args = ap.parse_args(argv)

    from benchmarks._sharded import (backend_ready, force_devices, leg_tag,
                                     validate_mesh_args)
    # Validate the 2-D layout BEFORE touching jax: the same rules
    # make_cells_mesh enforces, surfaced pre-init with the exact flags.
    err = validate_mesh_args(args.devices, args.model)
    if err:
        print(f"error: {err}")
        return 2
    if args.devices:
        force_devices(args.devices)   # before jax initializes its backend

    grid = build_grid(args.cells, args.ues, args.seed)
    print(f"grid: B={grid.b} cells x N={grid.n_ue} UEs x C={grid.num_cuts} "
          f"cuts, {args.steps} slots, policy={args.policy}, "
          f"backend={jax.default_backend()}")

    print("name,us_per_call,derived")
    dt_b, sps_b = bench_batched(grid, args.policy, args.steps, args.repeats)
    print(f"scenario_grid_batched[{grid.b}x{grid.n_ue}],{dt_b*1e6:.0f},"
          f"slots_per_s={sps_b:.0f}")
    dt_l, sps_l = bench_loop(grid, args.policy, args.steps, args.repeats)
    print(f"scenario_grid_loop[{grid.b}x{grid.n_ue}],{dt_l*1e6:.0f},"
          f"slots_per_s={sps_l:.0f}")

    if args.devices:
        tag = leg_tag(args.devices, args.model)
        if not backend_ready(args.devices):
            print(f"error: the sharded leg asked for {args.devices} "
                  f"devices but only {len(jax.devices())} are live (the "
                  "backend initialized before the device flag landed)")
            return 1
        from repro.launch.mesh import make_cells_mesh
        # Layout preconditions were validated pre-init; make_cells_mesh
        # re-checks them and raises an actionable ValueError either way.
        grid_sh = build_grid(args.cells, args.ues, args.seed)
        grid_sh.use_mesh(make_cells_mesh(args.devices,
                                         model=args.model))
        dt_s, sps_s = bench_batched(grid_sh, args.policy, args.steps,
                                    args.repeats)
        print(f"scenario_grid_sharded[{grid.b}x{grid.n_ue}"
              f"{tag}],{dt_s*1e6:.0f},"
              f"slots_per_s={sps_s:.0f}")
        print(f"scenario_grid_sharded_speedup[{grid.b}x{grid.n_ue}"
              f"{tag}],0,"
              f"sharded_over_batched={sps_s / sps_b:.2f}x")

    speedup = sps_b / sps_l
    print(f"scenario_grid_speedup[{grid.b}x{grid.n_ue}],0,"
          f"batched_over_loop={speedup:.1f}x")
    if args.gate <= 0:
        print(f"speedup: {speedup:.1f}x (gate disabled)")
        return 0
    ok = speedup >= args.gate
    print(f"speedup: {speedup:.1f}x "
          f"({'meets' if ok else 'BELOW'} the {args.gate:g}x acceptance bar)")
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
