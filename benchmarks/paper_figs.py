"""Paper-figure benchmarks (one per paper table/figure).

The artifact is built in this process by ``scripts/train_compare.py``'s
``build_artifacts`` at REPRO_BENCH_EPISODES (default 200) episodes, so
``python -m benchmarks.run`` is self-contained and reads no file it did not
write.
"""
from __future__ import annotations

import importlib.util
import os

import numpy as np

_TRAIN_COMPARE = os.path.join(os.path.dirname(__file__), "..", "scripts",
                              "train_compare.py")


def build(episodes: int | None = None) -> dict:
    """Train and evaluate the paper's agents in-process; returns the
    Figs. 3-5 artifact dict."""
    spec = importlib.util.spec_from_file_location("train_compare",
                                                  _TRAIN_COMPARE)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    eps = episodes or int(os.environ.get("REPRO_BENCH_EPISODES", "200"))
    return mod.build_artifacts(eps)


def fig3_convergence(art: dict):
    """Fig. 3: convergence of LyMDO vs joint PPO."""
    rows = []
    for name, rec in art["fig3"].items():
        curve = np.asarray(rec["reward_curve"])
        n = len(curve)
        early = curve[: max(n // 10, 1)].mean()
        late = curve[-max(n // 10, 1):].mean()
        # convergence episode: first sustained crossing of 95% of final level
        target = late - 0.05 * abs(late)
        conv = next((i for i in range(n) if curve[i:i + 25].mean() >= target),
                    n)
        rows.append({"algo": name, "reward_first10pct": float(early),
                     "reward_last10pct": float(late),
                     "convergence_episode": int(conv),
                     "train_s": rec["train_s"]})
    return rows


def fig4_rate_sweep(art: dict):
    """Fig. 4(a-d): E2E delay / energy / memory / queue vs arrival rate."""
    rows = []
    for rate, algos in art["fig4"].items():
        for algo, m in algos.items():
            rows.append({"rate": float(rate), "algo": algo,
                         "delay_s": m["delay"], "energy_J": m["energy"],
                         "mem_GB": m["mem"],
                         "q_energy_final": m["q_energy_final"]})
    return rows


def fig5_queue_stability(art: dict):
    """Fig. 5: energy-queue peaks under the slot-75..110 burst."""
    rows = []
    for task in ("alexnet", "resnet"):
        for algo in ("lymdo", "ppo_joint"):
            trace = art["fig5"][algo][f"{task}_queue"]
            rows.append({"task": task, "algo": algo,
                         "peak_queue": float(max(trace)),
                         "final_queue": float(trace[-1])})
        rows.append({"task": task, "algo": "reduction_vs_ppo",
                     "peak_queue": art[f"fig5_{task}_queue_reduction"],
                     "final_queue": None})
    return rows


def headline(art: dict) -> dict:
    # per-rate delay reduction vs joint PPO (positive = LyMDO faster)
    reductions = {}
    for rate, algos in art["fig4"].items():
        d_l = algos["lymdo"]["delay"]
        d_j = algos["ppo_joint"]["delay"]
        reductions[float(rate)] = 1.0 - d_l / d_j
    rates_won = sum(1 for v in reductions.values() if v > 0)
    return {
        "episodes": art["episodes"],
        "delay_reduction_at_2p5": art["headline_delay_reduction_vs_ppo"],
        "delay_reduction_by_rate": reductions,
        "mean_delay_reduction": float(np.mean(list(reductions.values()))),
        "rates_won": rates_won,
        "paper_claim": 0.30,
    }
