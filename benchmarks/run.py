"""Benchmark harness entry point (deliverable d): one experiment per paper
figure + kernel micro-benchmarks + the serving-engine A/B + the batched
scenario-grid A/B + the roofline table.

Prints ``name,us_per_call,derived`` CSV per experiment, as required, and
writes the canonical ``BENCH_N.json`` perf-trajectory artifact at the repo
root (currently ``BENCH_10.json``), which folds together:

* ``serving``       -- continuous-vs-sync replay latency, goodput,
                       slot-steps/sec, prefill-compile counts
                       (benchmarks/serving_latency.py, the old BENCH_6 body)
* ``chunked_prefill`` -- long-prompt flash-crowd A/B: chunked vs
                       whole-prompt admission, identical tokens asserted,
                       per-tick wall p50/p99
                       (benchmarks/serving_latency.chunked_prefill_ab)
* ``scenario_grid`` -- batched-vs-loop grid rollout throughput + speedup
                       (benchmarks/scenario_grid.bench_payload)
* ``kernels``       -- the kernel micro-benchmark rows
                       (benchmarks/kernels_micro.bench_all)
* ``sanitize_overhead`` -- per-tick p50 with the KV-pool sanitizer off vs
                       on, identical schedule, identical tokens
                       (benchmarks/serving_latency.sanitize_overhead)

``--json-only`` skips the slow paper-figure / ablation / roofline legs and
just measures + writes the JSON artifact (the CI bench leg uses this).
"""
from __future__ import annotations

import argparse
import json
import os
import time


def _row(name, us, derived):
    print(f"{name},{us:.1f},{derived}")


def build_bench_payload(*, grid_cells: int = 8, grid_ues: int = 4,
                        grid_steps: int = 24, grid_repeats: int = 2) -> dict:
    """Measure the five tracked subsystems and assemble the BENCH_10 body."""
    from . import kernels_micro, scenario_grid, serving_latency
    serving = serving_latency.bench_all()
    chunked = serving_latency.chunked_prefill_ab()
    kernels = [{"name": name, "us_per_call": round(us, 1), "derived": derived}
               for name, us, derived in kernels_micro.bench_all()]
    grid = scenario_grid.bench_payload(cells=grid_cells, ues=grid_ues,
                                       steps=grid_steps,
                                       repeats=grid_repeats)
    sanitize = serving_latency.sanitize_overhead()
    return {"bench": 10, "serving": serving, "chunked_prefill": chunked,
            "scenario_grid": grid, "kernels": kernels,
            "sanitize_overhead": sanitize}


def _emit_bench_rows(payload: dict) -> None:
    """Print the payload's measurements in the harness CSV convention."""
    from . import serving_latency
    for k in payload["kernels"]:
        _row(f"kernel[{k['name']}]", k["us_per_call"], k["derived"])
    for name, us, derived in serving_latency.rows(payload["serving"]):
        _row(name, us, derived)
    for name, us, derived in serving_latency.chunked_rows(
            payload["chunked_prefill"]):
        _row(name, us, derived)
    g = payload["scenario_grid"]
    shape = f"{g['config']['cells']}x{g['config']['ues']}"
    _row(f"scenario_grid[{shape}]", g["batched"]["best_seconds"] * 1e6,
         f"batched_slots_per_s={g['batched']['slots_per_s']:.0f}"
         f";loop_slots_per_s={g['loop']['slots_per_s']:.0f}"
         f";speedup={g['batched_speedup']:.2f}x")
    s = payload["sanitize_overhead"]
    _row("sanitize_overhead", s["p50_tick_us"]["off"],
         f"on_p50_us={s['p50_tick_us']['on']:.1f}"
         f";on_over_off={s['on_over_off']:.2f}x"
         f";outputs_match={'OK' if s['outputs_match'] else 'FAIL'}")


def _write_bench_json(payload: dict) -> None:
    bench_path = os.path.join(os.path.dirname(__file__), "..",
                              "BENCH_10.json")
    with open(bench_path, "w") as f:
        json.dump(payload, f, indent=1, sort_keys=True)
    _row("bench_json", 0.0, f"wrote={os.path.normpath(bench_path)}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--json-only", action="store_true",
                    help="measure and write BENCH_10.json only (skips the "
                         "paper-figure, ablation, and roofline legs)")
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    t_start = time.time()
    print("name,us_per_call,derived")

    if args.json_only:
        payload = build_bench_payload()
        _emit_bench_rows(payload)
        _write_bench_json(payload)
        _row("bench_total", (time.time() - t_start) * 1e6,
             "seconds=%.1f" % (time.time() - t_start))
        return 0

    # -- paper figures -------------------------------------------------------
    from . import paper_figs
    t0 = time.time()
    art = paper_figs.build()
    build_us = (time.time() - t0) * 1e6

    for r in paper_figs.fig3_convergence(art):
        _row(f"fig3_convergence[{r['algo']}]",
             r["train_s"] * 1e6 / max(art["episodes"], 1),
             f"final_reward={r['reward_last10pct']:.2f};conv_ep={r['convergence_episode']}")
    for r in paper_figs.fig4_rate_sweep(art):
        _row(f"fig4[{r['algo']}@{r['rate']}]", 0.0,
             f"delay={r['delay_s']:.4f}s;energy={r['energy_J']*1e3:.1f}mJ;"
             f"mem={r['mem_GB']*1e3:.0f}MB;qE={r['q_energy_final']:.1f}")
    for r in paper_figs.fig5_queue_stability(art):
        _row(f"fig5[{r['task']}:{r['algo']}]", 0.0,
             f"peak_queue={r['peak_queue']:.3f}")
    h = paper_figs.headline(art)
    by_rate = ";".join(f"@{r:g}={v*100:+.0f}%"
                       for r, v in sorted(h["delay_reduction_by_rate"].items()))
    _row("headline_delay_vs_ppo", build_us,
         f"won_{h['rates_won']}of5_rates;{by_rate}"
         f";mean={h['mean_delay_reduction']*100:+.1f}%_vs_paper_claim_30%"
         f";episodes={h['episodes']}"
         f";note=@2.5_PPO_violates_energy_budget_7x_queue")

    # -- Lyapunov V ablation (beyond-paper) ------------------------------------
    from . import ablation_v
    t0 = time.time()
    vrows = ablation_v.sweep(v_values=(1.0, 10.0, 100.0), episodes=2,
                             steps=200)
    for r in vrows:
        _row(f"ablation_v[V={r['V']:g}]", (time.time() - t0) * 1e6 / 3,
             f"delay={r['delay_s']:.4f}s;qE={r['q_energy_final']:.1f}")

    # -- kernels + serving A/Bs + scenario grid -> BENCH_10.json ---------------
    payload = build_bench_payload()
    _emit_bench_rows(payload)
    _write_bench_json(payload)

    # -- roofline (from dry-run artifacts; skip silently if sweep not run) -----
    from . import roofline
    dd = os.path.join(os.path.dirname(__file__), "out", "dryrun")
    if os.path.isdir(dd) and os.listdir(dd):
        rows = roofline.build_table(dd, "single")
        ok = [r for r in rows if r["status"] == "ok"]
        for r in ok:
            _row(f"roofline[{r['arch']}@{r['shape']}]", r["step_s"] * 1e6,
                 f"bound={r['dominant']};mfu_at_roof={r['roofline_fraction']*100:.1f}%"
                 f";useful={r['useful_fraction']*100:.0f}%")
        doms = {}
        for r in ok:
            doms[r["dominant"]] = doms.get(r["dominant"], 0) + 1
        _row("roofline_summary", 0.0,
             ";".join(f"{k}={v}" for k, v in sorted(doms.items())))
    else:
        _row("roofline_summary", 0.0, "dryrun_artifacts_missing")

    _row("bench_total", (time.time() - t_start) * 1e6,
         "seconds=%.1f" % (time.time() - t_start))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
