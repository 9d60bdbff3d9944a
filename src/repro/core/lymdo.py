"""LyMDO training/evaluation driver (Algorithm 1) and baseline runners.

An *episode* = K time slots (paper: K = 200); virtual queues reset at episode
start (Algorithm 1 line 5).  The replay memory holds exactly one episode and
is consumed by a PPO update when filled (lines 16-27).  Rollout + update are
one jitted program; episodes run under ``lax.scan`` in chunks so multi-
thousand-episode training (paper: 2000) takes seconds on CPU.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from .env import MecEnv, MecState, SlotResult
from .policies import JointGaussianPolicy
from .ppo import PPO, Trajectory, TrainState
from . import sweep


@dataclasses.dataclass(frozen=True)
class RunConfig:
    episodes: int = 500
    steps: int = 200           # K, slots per episode
    seed: int = 0
    chunk: int = 25            # episodes per jitted scan chunk (logging cadence)
    log: bool = True


def _summarize(results: SlotResult) -> dict:
    """Per-episode means/finals used by the paper's figures."""
    return {
        "reward": jnp.mean(results.reward),
        "delay": jnp.mean(jnp.mean(results.delay, axis=-1)),
        "energy": jnp.mean(jnp.mean(results.energy, axis=-1)),
        "mem": jnp.mean(jnp.mean(results.mem_cost, axis=-1)),
        "q_energy_final": jnp.mean(results.q_energy[-1]),
        "q_memory_final": jnp.mean(results.q_memory[-1]),
        "cut_mean": jnp.mean(results.cut.astype(jnp.float32)),
    }


class Runner:
    """Binds (env, agent) into jitted episode/train/eval programs.

    ``mode``:
      * "lymdo": agent picks the cut; convex optimization allocates resources
        (the paper's algorithm).
      * "joint": agent picks cut + alpha + f_ue + f_es (the paper's "PPO"
        baseline); requires a ``JointGaussianPolicy``.
    """

    def __init__(self, env: MecEnv, agent: PPO, steps: int = 200,
                 mode: str = "lymdo"):
        self.env, self.agent, self.steps, self.mode = env, agent, steps, mode
        if mode == "joint" and not isinstance(agent.policy, JointGaussianPolicy):
            raise ValueError("joint mode needs JointGaussianPolicy")
        self._train_chunk = jax.jit(self._make_train_chunk(), static_argnames="n")
        self._eval_episode = jax.jit(self._make_episode(deterministic=True))

    # -- inner programs ------------------------------------------------------

    def _apply(self, state: MecState, action):
        if self.mode == "joint":
            cut, alpha, f_ue, f_es = self.agent.policy.split(action)
            return self.env.step_joint(state, cut, alpha, f_ue, f_es)
        return self.env.step(state, self.agent.policy.to_cut(action))

    def _make_episode(self, deterministic: bool = False):
        env, agent = self.env, self.agent

        def episode(params, key):
            key, k0 = jax.random.split(key)
            st0 = env.reset(k0)

            def body(carry, _):
                st, key = carry
                key, k_act = jax.random.split(key)
                obs = env.observe(st)
                action, logp, value = agent.act(params, obs, k_act)
                if deterministic:
                    # mean/argmax action: Fig. 4 evaluates "well-trained
                    # offline" policies without exploration noise.
                    action = agent.policy.mean_action(params["pi"], obs)
                st2, res = self._apply(st, action)
                return (st2, key), (obs, action, logp, value, res)

            (st_end, _), (obs, action, logp, value, results) = jax.lax.scan(
                body, (st0, key), None, length=self.steps)
            last_value = agent.value(params, env.observe(st_end))
            traj = Trajectory(obs=obs, action=action, logp=logp,
                              reward=results.reward, value=value,
                              last_value=last_value)
            return traj, _summarize(results), results

        return episode

    def _make_train_chunk(self):
        episode = self._make_episode()

        def chunk(state: TrainState, key, n: int):
            def one(carry, k):
                st = carry
                traj, metrics, _ = episode(st.params, k)
                st, upd_metrics = self.agent.update(st, traj)
                metrics.update(upd_metrics)
                return st, metrics

            keys = jax.random.split(key, n)
            return jax.lax.scan(one, state, keys)

        return chunk

    # -- public API ----------------------------------------------------------

    def train(self, cfg: RunConfig = RunConfig()):
        key = jax.random.PRNGKey(cfg.seed)
        key, k_init = jax.random.split(key)
        state = self.agent.init(k_init)
        history: dict[str, list] = {}
        done = 0
        t0 = time.time()
        while done < cfg.episodes:
            n = min(cfg.chunk, cfg.episodes - done)
            key, k_chunk = jax.random.split(key)
            state, metrics = self._train_chunk(state, k_chunk, n=n)
            metrics = jax.tree.map(np.asarray, metrics)
            for k, val in metrics.items():
                history.setdefault(k, []).append(val)
            done += n
            if cfg.log:
                print(f"  ep {done:5d}/{cfg.episodes} "
                      f"reward {metrics['reward'][-1]:9.3f} "
                      f"delay {metrics['delay'][-1]:7.4f}s "
                      f"({time.time() - t0:5.1f}s)")
        history = {k: np.concatenate(v) for k, v in history.items()}
        return state, history

    def evaluate(self, state: TrainState, episodes: int = 10, seed: int = 1234):
        """Deterministic-policy evaluation; returns per-episode metric means
        and the full last-episode SlotResult (for Fig. 5-style traces)."""
        key = jax.random.PRNGKey(seed)
        all_metrics: dict[str, list] = {}
        results = None
        for _ in range(episodes):
            key, k = jax.random.split(key)
            _, metrics, results = self._eval_episode(state.params, k)
            for name, val in metrics.items():
                all_metrics.setdefault(name, []).append(float(val))
        return {k: float(np.mean(v)) for k, v in all_metrics.items()}, results


# ---------------------------------------------------------------------------
# Non-learning baselines (paper Sec. V-B: Local / Edge / Random + our Oracle).
# All reuse the exact convex allocators via env.step.
# ---------------------------------------------------------------------------

def run_fixed(env: MecEnv, cut_fn: Callable, episodes: int, steps: int,
              seed: int = 0):
    """cut_fn(state, key) -> (N,) int cuts.  Returns (metrics, last_results)."""

    def episode(key):
        key, k0 = jax.random.split(key)
        st0 = env.reset(k0)

        def body(carry, _):
            st, key = carry
            key, k = jax.random.split(key)
            st2, res = env.step(st, cut_fn(st, k))
            return (st2, key), res

        (_, _), results = jax.lax.scan(body, (st0, key), None, length=steps)
        return _summarize(results), results

    episode = jax.jit(episode)
    key = jax.random.PRNGKey(seed)
    agg: dict[str, list] = {}
    results = None
    for _ in range(episodes):
        key, k = jax.random.split(key)
        metrics, results = episode(k)
        for name, val in metrics.items():
            agg.setdefault(name, []).append(float(val))
    return {k: float(np.mean(v)) for k, v in agg.items()}, results


def local_cut_fn(env: MecEnv):
    return lambda st, key: env.L


def edge_cut_fn(env: MecEnv):
    return lambda st, key: jnp.zeros((env.n_ue,), jnp.int32)


def random_cut_fn(env: MecEnv):
    return lambda st, key: jax.random.randint(key, (env.n_ue,), 0, env.L + 1)


def oracle_cut_fn(env: MecEnv):
    return lambda st, key: sweep.oracle_cut(env, st)


# ---------------------------------------------------------------------------
# Batched multi-cell runners (see repro.core.scenarios): B cells x N UEs in a
# single jitted lax.scan program instead of one Python loop per cell.
# ---------------------------------------------------------------------------

def run_fixed_batched(grid, policy="oracle", episodes: int = 1,
                      steps: int = 200, seed: int = 0,
                      oracle_backend: str = "auto"):
    """Batched analogue of :func:`run_fixed` over a ``ScenarioGrid``.

    ``policy`` is a ``scenarios.POLICIES`` name or a per-cell callable
    ``(params, state, key) -> (N,) cuts``.  Returns (metrics, last_results):
    metrics maps each summary name to a (B,) per-cell mean over episodes;
    last_results is the final episode's (steps, B, N) SlotResult stack.

    A device-sharded grid (``grid.use_mesh(...)``; see repro.core.gridshard)
    is accepted transparently: the rollout runs partitioned over the mesh's
    "cells" axis -- and, on a ``("cells", "model")`` mesh
    (``use_mesh(model=M)``), with M-way per-cell tensor parallelism -- and
    still returns logical-B outputs that match the single-device run to
    1e-5.  ``oracle_backend`` picks the Oracle's sweep (see
    ``ScenarioGrid.objective_tables``).
    """
    rollout = grid.make_rollout(policy, steps, oracle_backend=oracle_backend)
    key = jax.random.PRNGKey(seed)
    agg: dict[str, list] = {}
    results = None
    for _ in range(episodes):
        key, k = jax.random.split(key)
        _, results, summary = rollout(k)
        for name, val in summary.items():
            agg.setdefault(name, []).append(np.asarray(val))
    return {k: np.mean(np.stack(v), axis=0) for k, v in agg.items()}, results


def eval_policy_batched(grid, agent: PPO, train_state: TrainState,
                        episodes: int = 1, steps: int = 200, seed: int = 1234):
    """Deterministic-policy LyMDO evaluation across every cell of a grid.

    The single trained agent (shared weights) acts per cell on that cell's
    observation; all cells advance in one scan (device-sharded grids work
    transparently, as in :func:`run_fixed_batched`).  Cells must share the
    agent's obs/action dims (guaranteed by ScenarioGrid's common UE count)
    AND the per-UE layer counts the policy head was built with: ``to_cut``
    maps actions onto the policy's own L, so a grid cell with deeper
    profiles would silently never receive the deep cuts.
    """
    from .env import observe_p

    pol_L = np.asarray(agent.policy.num_layers)
    grid_L = np.asarray(grid.params.L)
    if not np.array_equal(np.broadcast_to(pol_L, grid_L.shape), grid_L):
        raise ValueError(
            f"policy layer counts {pol_L} do not match every grid cell's L "
            f"{grid_L}; eval_policy_batched needs cells with the profiles "
            "the policy was trained for")

    pi_params = train_state.params["pi"]

    def act(params, state, key):
        del key
        obs = observe_p(params, state)
        y = agent.policy.mean_action(pi_params, obs)
        return agent.policy.to_cut(y)

    return run_fixed_batched(grid, act, episodes=episodes, steps=steps,
                             seed=seed)
