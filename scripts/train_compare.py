"""The full paper experiment: trains, evaluates, writes the artifact.

``build_artifacts(episodes)`` trains and evaluates in the calling process
(``benchmarks/paper_figs.py`` calls it that way); run as a script it writes
scripts/out/paper_artifacts.json:

  PYTHONPATH=src python scripts/train_compare.py [episodes]

The artifact holds:
  * fig3: reward curves (LyMDO, LyMDO-categorical, PPO-joint)
  * fig4: {delay, energy, mem, qE} x arrival rate x algorithm
  * fig5: per-slot energy-queue traces at lam=2.5 peak pattern
  * headline: delay reduction vs joint PPO at lam=2.5

Evaluation runs on the scenario registry: the Fig. 4 rate sweep is ONE
``ScenarioGrid`` of ``fixed_rate`` cells (every rate rolls out in a single
jitted batched program, device-sharded over a ``("cells",)`` mesh when
more than one device is live) and Fig. 5 is the ``peak_window`` scenario.
Only the joint-PPO baseline still evaluates per-env: it allocates
resources itself (``env.step_joint``), which the cut-policy grid rollout
deliberately does not model.
"""
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax

from repro.core.lymdo import (Runner, RunConfig, eval_policy_batched,
                              run_fixed_batched)
from repro.core.policies import (CategoricalPolicy, GaussianTanhPolicy,
                                 JointGaussianPolicy)
from repro.core.ppo import PPO, PPOConfig
from repro.core.scenarios import grid_from_names, make

RATES = [0.5, 1.0, 1.5, 2.0, 2.5]
EVAL_EPISODES = 5
STEPS = 200
OUT = os.path.join(os.path.dirname(__file__), "out")


def build_artifacts(episodes: int) -> dict:
    """Train LyMDO (Gaussian and categorical) and joint PPO for ``episodes``
    episodes each on Table I, then evaluate Figs. 3-5."""
    train_env = make("paper_table1").build()   # Table I, iid-uniform rates
    artifacts = {"episodes": episodes, "rates": RATES}

    agents = {}
    for name, policy_cls, mode in [
            ("lymdo", GaussianTanhPolicy, "lymdo"),
            ("lymdo_categorical", CategoricalPolicy, "lymdo"),
            ("ppo_joint", JointGaussianPolicy, "joint")]:
        t0 = time.time()
        if policy_cls is JointGaussianPolicy:
            pol = policy_cls(train_env.obs_dim, train_env.L,
                             train_env.cfg.f_max_ue, train_env.cfg.f_max_es)
        else:
            pol = policy_cls(train_env.obs_dim, train_env.L)
        agent = PPO(pol, train_env.obs_dim, PPOConfig())
        runner = Runner(train_env, agent, steps=STEPS, mode=mode)
        state, hist = runner.train(RunConfig(episodes=episodes, steps=STEPS,
                                             chunk=50))
        agents[name] = (agent, state, mode)
        artifacts.setdefault("fig3", {})[name] = {
            "reward_curve": [float(x) for x in hist["reward"]],
            "train_s": time.time() - t0,
        }
        print(f"[trained] {name} in {time.time()-t0:.0f}s", flush=True)

    # ---- Fig. 4: sweep arrival rates as ONE batched grid --------------------
    # One fixed_rate cell per sweep point; every rate evaluates in a single
    # jitted rollout per policy instead of a Python loop over envs.
    grid = grid_from_names([("fixed_rate", {"rate": r}) for r in RATES])
    if jax.device_count() > 1:
        grid.use_mesh()                        # ("cells",) over live devices

    fig4 = {str(r): {} for r in RATES}

    def record(name, metrics):
        """metrics: summary name -> (B,) per-cell means; fan out to rates."""
        for b, rate in enumerate(RATES):
            fig4[str(rate)][name] = {k: float(v[b]) for k, v in metrics.items()}

    for name in ("lymdo", "lymdo_categorical"):
        agent, state, _ = agents[name]
        metrics, _ = eval_policy_batched(grid, agent, state,
                                         episodes=EVAL_EPISODES, steps=STEPS)
        record(name, metrics)
    for name in ("local", "edge", "random", "oracle"):
        metrics, _ = run_fixed_batched(grid, name, episodes=EVAL_EPISODES,
                                       steps=STEPS)
        record(name, metrics)

    # joint PPO allocates resources itself (env.step_joint): per-env eval
    agent_j, state_j, mode_j = agents["ppo_joint"]
    for rate in RATES:
        env_r = make("fixed_rate", rate=rate).build()
        m, _ = Runner(env_r, agent_j, steps=STEPS, mode=mode_j).evaluate(
            state_j, episodes=EVAL_EPISODES)
        fig4[str(rate)]["ppo_joint"] = {k: float(v) for k, v in m.items()}

    for rate in RATES:
        row = fig4[str(rate)]
        print(f"[fig4] rate {rate}: lymdo delay {row['lymdo']['delay']:.4f} "
              f"ppo {row['ppo_joint']['delay']:.4f} "
              f"local {row['local']['delay']:.4f}", flush=True)
    artifacts["fig4"] = fig4

    d_l = fig4["2.5"]["lymdo"]["delay"]
    d_j = fig4["2.5"]["ppo_joint"]["delay"]
    artifacts["headline_delay_reduction_vs_ppo"] = 1.0 - d_l / d_j
    best = min(d_l, fig4["2.5"]["lymdo_categorical"]["delay"])
    artifacts["headline_delay_reduction_best"] = 1.0 - best / d_j

    # ---- Fig. 5: queue stability under peak workload ------------------------
    fig5 = {}
    peak_grid = grid_from_names([("peak_window", {"boost": 1.0})])
    agent_l, state_l, _ = agents["lymdo"]
    _, results = eval_policy_batched(peak_grid, agent_l, state_l,
                                     episodes=1, steps=STEPS)
    qe_traces = {"lymdo": np.asarray(results.q_energy)[:, 0, :]}  # (steps, N)
    env_p = make("peak_window", boost=1.0).build()
    _, results_j = Runner(env_p, agent_j, steps=STEPS, mode=mode_j).evaluate(
        state_j, episodes=1)
    qe_traces["ppo_joint"] = np.asarray(results_j.q_energy)
    for name, qe in qe_traces.items():
        fig5[name] = {
            "alexnet_queue": qe[:, :2].mean(1).tolist(),   # UEs 0-1: AlexNet
            "resnet_queue": qe[:, 2:].mean(1).tolist(),    # UEs 2-4: ResNet18
        }
    artifacts["fig5"] = fig5
    for task, idx in [("alexnet", "alexnet_queue"),
                      ("resnet", "resnet_queue")]:
        peak_l = max(fig5["lymdo"][idx])
        peak_j = max(fig5["ppo_joint"][idx])
        artifacts[f"fig5_{task}_queue_reduction"] = \
            1.0 - peak_l / max(peak_j, 1e-9)
    return artifacts


def main():
    episodes = int(sys.argv[1]) if len(sys.argv) > 1 else 1500
    artifacts = build_artifacts(episodes)
    os.makedirs(OUT, exist_ok=True)
    with open(os.path.join(OUT, "paper_artifacts.json"), "w") as f:
        json.dump(artifacts, f)
    print("headline: %.1f%% delay reduction vs joint PPO (best %.1f%%)"
          % (100 * artifacts["headline_delay_reduction_vs_ppo"],
             100 * artifacts["headline_delay_reduction_best"]), flush=True)
    print("saved paper_artifacts.json")


if __name__ == "__main__":
    main()
