"""Scenario registry + batched multi-cell evaluation engine.

The paper evaluates one cell (N UEs, one ES, Table I constants).  Scaling the
reproduction to "many cells x many UE populations x many arrival processes"
needs two things:

1. **A registry of named scenario constructors** -- each returns a
   :class:`Scenario` (profiles + budgets + ``MecConfig`` + channel geometry)
   so sweeps are declared by name/knobs instead of hand-built envs.  See
   ``docs/scenarios.md`` for the catalogue and how to add one.

2. **A batched engine** -- a :class:`ScenarioGrid` stacks B single-cell
   ``MecParams`` pytrees into one (B, ...) pytree (``stack_params``) and
   evaluates all cells with ``jax.vmap`` over the pure ``step_p`` /
   ``objective_table_p`` functions, wrapped in a single ``lax.scan`` over
   time slots.  One jitted program replaces the per-cell Python loop.

The batched Oracle's hot inner loop (the (B, N, C) objective table) routes
through the ``partition_sweep`` Pallas kernel on TPU (one launch for all
cells, ``n_total`` pinned to the per-cell UE count) and falls back to the
checked ``kernels.ref`` / pure-lax path elsewhere.

3. **A device-sharded grid** -- ``ScenarioGrid.use_mesh`` places the stacked
   (B, ...) pytree over a ``("cells",)`` device mesh
   (``repro.launch.mesh.make_cells_mesh``) with ``NamedSharding``; uneven B
   is padded to a device multiple with a validity mask
   (``repro.core.gridshard``).  ``use_mesh(model=M)`` activates the 2-D
   ``("cells", "model")`` mesh: M-way per-cell tensor parallelism over each
   cell's UE axis on top of the cell split.  The jitted rollout is
   unchanged -- GSPMD partitions the vmap+scan over devices -- and sharded
   rollouts (1-D or 2-D) match single-device ones to 1e-5 for EVERY
   registered scenario (padded cells never pollute summaries; pinned by
   tests/test_gridshard.py's registry-wide parity suite).
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..profiling.profiles import LayerProfile
from ..traffic import processes as traffic
from . import gridshard, sweep
from .env import (LAM_FIXED, LAM_PEAK, LAM_TRACE, MecConfig,
                  MecEnv, MecParams, MecState, SlotResult, free_space_gain,
                  make_params, reset_p, step_p)

# Scalars the Pallas sweep kernel bakes in at compile time; the kernel route
# is only available when these agree across every cell of a grid.
_SWEEP_SCALARS = ("rho", "kappa", "p_tx", "w_hz", "n0", "f_max_ue",
                  "f_max_es", "v", "gamma_ue", "gamma_es", "stability_margin")


# ---------------------------------------------------------------------------
# Scenario spec
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Scenario:
    """Declarative single-cell scenario: everything needed to build an env."""

    name: str
    cfg: MecConfig
    profiles: tuple[LayerProfile, ...]
    e_budget: tuple[float, ...]
    c_budget: tuple[float, ...]
    mean_gain: float | None = None          # None -> paper free-space default
    lam_fixed: tuple[float, ...] | None = None
    arrival: object | None = None           # explicit repro.traffic process
                                            # (overrides cfg.lam_mode)
    description: str = ""

    @property
    def n_ue(self) -> int:
        return len(self.profiles)

    def build(self) -> MecEnv:
        return MecEnv(list(self.profiles), self.cfg, list(self.e_budget),
                      list(self.c_budget), mean_gain=self.mean_gain,
                      lam_fixed=None if self.lam_fixed is None
                      else list(self.lam_fixed), arrival=self.arrival)

    def params(self) -> MecParams:
        return make_params(list(self.profiles), self.cfg, list(self.e_budget),
                           list(self.c_budget), mean_gain=self.mean_gain,
                           lam_fixed=None if self.lam_fixed is None
                           else list(self.lam_fixed), arrival=self.arrival)

    def sweep_scalars(self) -> dict:
        """Host-side constants for the Pallas partition-sweep route."""
        return {k: float(getattr(self.cfg, k)) for k in _SWEEP_SCALARS}


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

_REGISTRY: dict[str, Callable[..., Scenario]] = {}


def register(name: str):
    """Decorator: register a named scenario constructor."""
    def deco(fn):
        if name in _REGISTRY:
            raise ValueError(f"scenario {name!r} already registered")
        _REGISTRY[name] = fn
        fn.scenario_name = name
        return fn
    return deco


def names() -> tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


def make(name: str, **knobs) -> Scenario:
    """Build a registered scenario by name (knobs forwarded verbatim)."""
    try:
        ctor = _REGISTRY[name]
    except KeyError:
        raise KeyError(f"unknown scenario {name!r}; have {names()}") from None
    return ctor(**knobs)


def describe() -> str:
    lines = []
    for name in names():
        doc = (_REGISTRY[name].__doc__ or "").strip().splitlines()
        lines.append(f"{name}: {doc[0] if doc else ''}")
    return "\n".join(lines)


# ---------------------------------------------------------------------------
# Built-in scenario constructors
# ---------------------------------------------------------------------------

def _paper_fleet(n_alexnet: int, n_resnet: int):
    from ..profiling.convnets import alexnet_profile, resnet18_profile
    profiles = ([alexnet_profile()] * n_alexnet
                + [resnet18_profile()] * n_resnet)
    e = (0.040,) * n_alexnet + (0.060,) * n_resnet
    c = (0.100,) * n_alexnet + (0.030,) * n_resnet
    return tuple(profiles), e, c


@register("paper_table1")
def paper_table1(n_alexnet: int = 2, n_resnet: int = 3,
                 cfg: MecConfig = MecConfig()) -> Scenario:
    """Paper Sec. V-A / Table I: 2x AlexNet + 3x ResNet18, iid-uniform rates."""
    profiles, e, c = _paper_fleet(n_alexnet, n_resnet)
    return Scenario(name="paper_table1", cfg=cfg, profiles=profiles,
                    e_budget=e, c_budget=c,
                    description="paper Table I single cell")


@register("fixed_rate")
def fixed_rate(rate: float = 2.5, n_alexnet: int = 2,
               n_resnet: int = 3) -> Scenario:
    """Fig. 4 sweep point: constant per-UE arrival rate (req/s)."""
    profiles, e, c = _paper_fleet(n_alexnet, n_resnet)
    n = len(profiles)
    return Scenario(name=f"fixed_rate[{rate:g}]",
                    cfg=MecConfig(lam_mode=LAM_FIXED),
                    profiles=profiles, e_budget=e, c_budget=c,
                    lam_fixed=(float(rate),) * n,
                    description=f"Fig. 4 fixed-rate cell @ {rate:g} req/s")


@register("peak_window")
def peak_window(base_rate: float = 2.5, boost: float = 1.0, start: int = 75,
                stop: int = 110) -> Scenario:
    """Fig. 5 stability run: constant base rate + a peak-workload window."""
    profiles, e, c = _paper_fleet(2, 3)
    n = len(profiles)
    cfg = MecConfig(lam_mode=LAM_PEAK, peak_start=int(start),
                    peak_stop=int(stop), peak_boost=float(boost))
    return Scenario(name=f"peak_window[{base_rate:g}+{boost:g}]",
                    cfg=cfg, profiles=profiles, e_budget=e, c_budget=c,
                    lam_fixed=(float(base_rate),) * n,
                    description="Fig. 5 peak-workload cell")


@register("hetero_fleet")
def hetero_fleet(n_ue: int = 8, seed: int = 0,
                 rate_range: tuple[float, float] = (0.5, 2.5)) -> Scenario:
    """Heterogeneous fleet: random AlexNet/ResNet mix, budgets and rates."""
    from ..profiling.convnets import alexnet_profile, resnet18_profile
    rng = np.random.default_rng(seed)
    pool = (alexnet_profile(), resnet18_profile())
    picks = rng.integers(0, len(pool), n_ue)
    profiles = tuple(pool[i] for i in picks)
    e = tuple(float(x) for x in rng.uniform(0.030, 0.080, n_ue))
    c = tuple(float(x) for x in rng.uniform(0.025, 0.120, n_ue))
    lam = tuple(float(x) for x in rng.uniform(*rate_range, n_ue))
    return Scenario(name=f"hetero_fleet[{n_ue}@{seed}]",
                    cfg=MecConfig(lam_mode=LAM_FIXED),
                    profiles=profiles, e_budget=e, c_budget=c,
                    lam_fixed=lam,
                    description="random device/budget/rate mix")


@register("mmpp_burst")
def mmpp_burst(seed: int = 0, rates: tuple[float, ...] = (0.5, 3.0),
               p_stay: float = 0.92, horizon: int = 400,
               n_alexnet: int = 2, n_resnet: int = 3) -> Scenario:
    """Bursty cell: per-UE Markov-modulated (MMPP) rates over the paper fleet."""
    profiles, e, c = _paper_fleet(n_alexnet, n_resnet)
    arrival = traffic.make_mmpp(len(profiles), seed=seed, rates=rates,
                                p_stay=p_stay, horizon=horizon)
    return Scenario(name=f"mmpp_burst[{seed}]", cfg=MecConfig(),
                    profiles=profiles, e_budget=e, c_budget=c,
                    arrival=arrival,
                    description="Markov-modulated bursty arrivals "
                                f"(regimes {rates}, p_stay={p_stay:g})")


@register("diurnal")
def diurnal(base: float = 1.5, amp: float = 1.0, period: float = 200.0,
            phase: float = 0.0, n_alexnet: int = 2,
            n_resnet: int = 3) -> Scenario:
    """Day/night cell: sinusoidal arrival rates around a base load."""
    profiles, e, c = _paper_fleet(n_alexnet, n_resnet)
    n = len(profiles)
    arrival = traffic.Diurnal(base=traffic.per_ue(base, n),
                              amp=traffic.per_ue(amp, n),
                              period=jnp.float32(period),
                              phase=jnp.float32(phase))
    return Scenario(name=f"diurnal[{base:g}±{amp:g}]", cfg=MecConfig(),
                    profiles=profiles, e_budget=e, c_budget=c,
                    arrival=arrival,
                    description=f"sinusoidal load, period {period:g} slots")


@register("flash_crowd")
def flash_crowd(base: float = 1.0, spike: float = 2.5, t0: int = 100,
                decay: float = 30.0, n_alexnet: int = 2,
                n_resnet: int = 3) -> Scenario:
    """Flash-crowd cell: base load + an exponentially decaying spike at t0."""
    profiles, e, c = _paper_fleet(n_alexnet, n_resnet)
    n = len(profiles)
    arrival = traffic.FlashCrowd(base=traffic.per_ue(base, n),
                                 spike=jnp.float32(spike),
                                 t0=jnp.int32(t0), decay=jnp.float32(decay))
    return Scenario(name=f"flash_crowd[{spike:g}@{t0}]", cfg=MecConfig(),
                    profiles=profiles, e_budget=e, c_budget=c,
                    arrival=arrival,
                    description=f"flash crowd +{spike:g} req/s at slot {t0}")


@register("trace_replay")
def trace_replay(trace=None, path: str | None = None, offset: int = 0,
                 seed: int = 0, rate_range: tuple[float, float] = (0.5, 2.5),
                 ) -> Scenario:
    """Replay a recorded arrival trace (repro.traffic.Trace) as the cell load.

    ``trace`` is a :class:`repro.traffic.Trace` (or ``path`` names a saved
    ``.npz``); the cell's fleet is a ``hetero_fleet`` sized to the trace's UE
    count.  ``offset`` rotates the trace so B cells built from one recording
    replay de-phased copies (per-cell diversity without per-cell recordings).

    With neither ``trace`` nor ``path``, a small deterministic MMPP demo
    trace is materialized (every registry constructor must build with zero
    args -- the contract the registry-wide parity suite relies on; see
    docs/scenarios.md).
    """
    from ..traffic.trace import Trace, from_process
    if trace is None:
        if path is None:
            proc = traffic.make_mmpp(4, seed=seed, horizon=64)
            trace = from_process(proc, 64)
        else:
            trace = Trace.load(path)
    if offset:
        trace = trace.shifted(offset)
    cell = hetero_fleet(n_ue=trace.n_ue, seed=seed, rate_range=rate_range)
    return dataclasses.replace(
        cell, name=f"trace_replay[{trace.n_ue}ue+{offset}]",
        cfg=MecConfig(lam_mode=LAM_TRACE), arrival=trace.process(),
        lam_fixed=None,
        description=f"replays a {trace.n_slots}-slot recorded trace "
                    f"(offset {offset})")


def multicell_grid(cells: int = 16, ues: int = 8, seed: int = 0,
                   d_min_m: float = 60.0, d_max_m: float = 300.0,
                   rate_range: tuple[float, float] = (0.5, 2.5),
                   uniform_scalars: bool = True) -> list[Scenario]:
    """B independent cells for one batched grid: each cell is a heterogeneous
    fleet at its own ES distance (per-cell mean channel gain).

    ``uniform_scalars=True`` keeps every ``MecConfig`` scalar at Table I
    values so the grid qualifies for the single-launch Pallas sweep route.
    """
    rng = np.random.default_rng(seed)
    out = []
    for b in range(cells):
        cell = hetero_fleet(n_ue=ues, seed=seed * 10_007 + b,
                            rate_range=rate_range)
        dist = float(rng.uniform(d_min_m, d_max_m))
        cfg = cell.cfg
        if not uniform_scalars:
            cfg = dataclasses.replace(cfg, v=float(rng.uniform(5.0, 20.0)))
        out.append(dataclasses.replace(
            cell, name=f"cell[{b}]@{dist:.0f}m", cfg=cfg,
            mean_gain=free_space_gain(distance_m=dist),
            description=f"grid cell {b}, ES distance {dist:.0f} m"))
    return out


# ---------------------------------------------------------------------------
# Stacking
# ---------------------------------------------------------------------------

def _pad_cuts(p: MecParams, cmax: int) -> MecParams:
    """Pad a cell's cut axis to ``cmax`` columns.

    Per-cut tables are constant for c >= L_n (cumsum/max of zero padding), so
    edge replication preserves semantics; raw per-layer tables get zeros
    (there is no layer there), and psi's edge value is already 0.
    """
    c = p.num_cuts
    if c == cmax:
        return p
    pad_edge = lambda t: jnp.pad(t, ((0, 0), (0, cmax - c)), mode="edge")
    pad_zero = lambda t: jnp.pad(t, ((0, 0), (0, cmax - c)))
    return dataclasses.replace(
        p,
        macs=pad_zero(p.macs), param_bytes=pad_zero(p.param_bytes),
        act_bytes=pad_zero(p.act_bytes),
        prefix_macs=pad_edge(p.prefix_macs),
        suffix_macs=pad_edge(p.suffix_macs),
        psi=pad_zero(p.psi),
        prefix_params=pad_edge(p.prefix_params),
        suffix_params=pad_edge(p.suffix_params),
        prefix_act_max=pad_edge(p.prefix_act_max),
        suffix_act_max=pad_edge(p.suffix_act_max))


def stack_params(params_list: Sequence[MecParams]) -> MecParams:
    """Stack B single-cell param pytrees into one (B, ...) pytree.

    Cells must share the UE count; the cut axis is padded to the widest cell.
    ``edge_queueing`` (a static field) must agree across cells, and so must
    the arrival-process *type* (and its array shapes, e.g. trace horizons) --
    the process class is part of the treedef the vmap dispatches on.
    """
    if not params_list:
        raise ValueError("need at least one cell")
    n_ues = {p.n_ue for p in params_list}
    if len(n_ues) != 1:
        raise ValueError(f"cells must share the UE count, got {sorted(n_ues)}")
    eq = {p.edge_queueing for p in params_list}
    if len(eq) != 1:
        raise ValueError("cells must share edge_queueing (static field)")
    kinds = {type(p.arrival) for p in params_list}
    if len(kinds) != 1:
        raise ValueError(
            "cells must share the arrival-process type (it is static "
            "treedef, like edge_queueing); got "
            f"{sorted(k.__name__ for k in kinds)}")
    cmax = max(p.num_cuts for p in params_list)
    padded = [_pad_cuts(p, cmax) for p in params_list]
    return jax.tree.map(lambda *xs: jnp.stack(xs), *padded)


# ---------------------------------------------------------------------------
# Batched policies (per-cell signature; the grid vmaps them over cells)
# ---------------------------------------------------------------------------

def oracle_policy(params: MecParams, state: MecState, key) -> jax.Array:
    """Decoupled per-slot argmin over the (N, C) objective table (lax path)."""
    del key
    return sweep.oracle_cut_p(params, state)


def local_policy(params: MecParams, state: MecState, key) -> jax.Array:
    del state, key
    return params.L


def edge_policy(params: MecParams, state: MecState, key) -> jax.Array:
    del state
    return jnp.zeros((params.n_ue,), jnp.int32)


def random_policy(params: MecParams, state: MecState, key) -> jax.Array:
    return jax.random.randint(key, (params.n_ue,), 0, params.L + 1)


POLICIES: dict[str, Callable] = {
    "oracle": oracle_policy,
    "local": local_policy,
    "edge": edge_policy,
    "random": random_policy,
}


# ---------------------------------------------------------------------------
# Batched engine
# ---------------------------------------------------------------------------

class ScenarioGrid:
    """B independent cells evaluated as one program.

    ``params`` is the stacked (B, ...) ``MecParams`` pytree; ``reset`` /
    ``step`` are vmapped over cells; ``make_rollout`` returns one jitted
    ``lax.scan`` over time slots that advances every cell per iteration.

    ``use_mesh`` (or the ``mesh=`` constructor arg) additionally shards the
    grid over a device mesh's ``"cells"`` axis: params are padded to a device
    multiple and placed with ``NamedSharding``, and the same rollout program
    then runs partitioned across devices.  ``params`` always stays the
    logical unpadded stack; the padded/placed copy lives in ``_run_params``
    and is selected automatically from a state batch's width.
    """

    def __init__(self, scenarios: Sequence[Scenario], mesh=None):
        self.scenarios = tuple(scenarios)
        if not self.scenarios:
            raise ValueError("empty grid")
        self.params = stack_params([s.params() for s in self.scenarios])
        self.b = len(self.scenarios)
        self.n_ue = self.scenarios[0].n_ue
        self.num_cuts = int(self.params.num_cuts)
        # Host-side kernel scalars, shared across cells or None.
        per_cell = [s.sweep_scalars() for s in self.scenarios]
        self.sweep_scalars = per_cell[0] if all(
            s == per_cell[0] for s in per_cell) else None
        self.gridshard: gridshard.GridSharding | None = None
        self._run_params = self.params
        if mesh is not None:
            self.use_mesh(mesh)

    # -- device sharding ----------------------------------------------------

    @property
    def b_run(self) -> int:
        """Cell-axis width the jitted programs run at (b, or b padded to a
        device multiple when sharded)."""
        return self.b if self.gridshard is None else self.gridshard.b_padded

    def use_mesh(self, mesh=None, *, model: int = 1,
                 pad_to: int | None = None):
        """Shard the stacked grid over ``mesh``'s ``"cells"`` axis.

        ``mesh=None`` builds a mesh over every live device
        (``repro.launch.mesh.make_cells_mesh``); ``model=M > 1`` makes it
        the 2-D ``("cells", "model")`` mesh -- M-way per-cell tensor
        parallelism, spreading the post-cell dim of every stacked table
        (the UE axis of params/states, hence the rows of the (B, N, C)
        objective sweep) over the model axis where divisible.  A mesh passed
        explicitly must agree with a non-default ``model``.

        B is padded up to a multiple of the cell-shard count (``pad_to``
        forces a wider pad -- mainly for tests); padded cells replicate the
        last real cell and are masked out of every rollout summary.
        Sharded rollouts -- 1-D or 2-D -- equal unsharded ones to 1e-5.
        Returns ``self``.
        """
        if mesh is None:
            from ..launch.mesh import make_cells_mesh
            mesh = make_cells_mesh(model=model)
        elif model != 1:
            have = dict(mesh.shape).get(gridshard.MODEL_AXIS, 1)
            if have != model:
                raise ValueError(
                    f"use_mesh(model={model}) but the given mesh has a "
                    f"{have}-way {gridshard.MODEL_AXIS!r} axis; pass "
                    "mesh=None to build a matching one (make_cells_mesh)")
        gs = gridshard.plan(self.b, mesh, pad_to=pad_to)
        padded = gridshard.pad_cells(self.params, gs)
        placed = gridshard.place(padded, gs)
        if gs.n_model > 1 and jax.tree.leaves(padded.arrival):
            # Arrival leaves put the model axis on their LAST dim (the UE
            # axis): their post-cell dim is per-slot time -- e.g. a
            # (B, T, N) trace -- which every step indexes, and sharding it
            # would gather across model shards once per slot.
            placed = dataclasses.replace(
                placed, arrival=gridshard.place(padded.arrival, gs,
                                                model_dim=-1))
        self._run_params = placed
        self.gridshard = gs
        return self

    def _params_for(self, states: MecState) -> MecParams:
        """Pick the params stack matching a state batch's cell-axis width."""
        lead = states.t.shape[0]
        if lead == self.b_run:
            return self._run_params
        if lead == self.b:
            return self.params
        raise ValueError(
            f"state batch {lead} matches neither b={self.b} nor the padded "
            f"width {self.b_run}")

    # -- per-slot primitives ------------------------------------------------

    def reset(self, key: jax.Array) -> MecState:
        """Stacked (b_run, ...) states from one key.

        Per-cell keys come from ``gridshard.cell_keys`` (fold_in over the
        cell index), so cell i draws the same randomness at any padding.
        """
        keys = gridshard.cell_keys(key, self.b, self.b_run)
        states = jax.vmap(reset_p)(self._run_params, keys)
        if self.gridshard is not None:
            states = gridshard.constrain(states, self.gridshard)
        return states

    def step(self, states: MecState,
             cuts: jax.Array) -> tuple[MecState, SlotResult]:
        """(B, N) cuts -> stacked next states + (B, N) slot results."""
        return jax.vmap(step_p)(self._params_for(states), states, cuts)

    # -- batched oracle sweep ----------------------------------------------

    def objective_tables(self, states: MecState, *, backend: str = "auto",
                         interpret: bool | None = None) -> jax.Array:
        """(B, N, C) drift-plus-penalty tables for every cell at once.

        backend:
          * ``"pallas"`` -- one ``partition_sweep`` kernel launch over the
            flattened (B*N, C) grid, one per cell shard on a sharded grid
            (requires uniform kernel scalars across cells;
            ``interpret=True`` off-TPU).
          * ``"ref"``    -- ``kernels.ref`` checked fallback (vmapped).
          * ``"lax"``    -- vmapped ``sweep.objective_table_p``.
          * ``"auto"``   -- pallas on TPU when eligible, else lax.
        """
        p = self._params_for(states)
        if backend == "auto":
            backend = ("pallas" if self.sweep_scalars is not None
                       and jax.default_backend() == "tpu" else "lax")
        if backend == "lax":
            return jax.vmap(sweep.objective_table_p)(p, states)
        if self.sweep_scalars is None:
            raise ValueError(
                "kernel scalars differ across cells; use backend='lax'")
        tables = (p.macs, p.param_bytes, p.act_bytes, p.psi, p.L,
                  states.lam, states.gain, states.queues.energy,
                  states.queues.memory)
        if backend == "ref":
            from ..kernels.ref import partition_sweep_batched_ref
            return partition_sweep_batched_ref(*tables, self.sweep_scalars)
        if backend == "pallas":
            from ..kernels.ops import partition_sweep_batched
            if interpret is None:
                interpret = jax.default_backend() != "tpu"

            def sweep_fn(*tables):
                return partition_sweep_batched(*tables, self.sweep_scalars,
                                               interpret=interpret)
            if self.gridshard is not None and p is self._run_params:
                sweep_fn = gridshard.per_shard(sweep_fn, self.gridshard)
            return sweep_fn(*tables)
        raise ValueError(f"unknown backend {backend!r}")

    def oracle_cuts(self, states: MecState, *, backend: str = "auto",
                    interpret: bool | None = None) -> jax.Array:
        """Batched Oracle decision: argmin over each cell's objective table."""
        table = self.objective_tables(states, backend=backend,
                                      interpret=interpret)
        return jnp.argmin(table, axis=-1).astype(jnp.int32)

    # -- rollout ------------------------------------------------------------

    def make_rollout(self, policy: str | Callable = "oracle",
                     steps: int = 200, oracle_backend: str = "auto"):
        """One jitted program: reset all cells, scan ``steps`` slots.

        ``policy`` is a registry name or a per-cell callable
        ``(params, state, key) -> (N,) cuts`` (vmapped over cells here).
        The ``"oracle"`` policy's per-slot sweep goes through
        ``oracle_cuts``/``objective_tables`` with ``oracle_backend`` --
        i.e. the single-launch Pallas kernel on TPU, lax elsewhere.
        Returns ``fn(key) -> (final_states, results, summary)`` with results
        stacked (steps, B, N) and summary per-cell (B,) means.

        On a sharded grid the identical program runs at the padded width
        with GSPMD partitioning the cell axis; padded cells are masked out
        of the summary and sliced off results/states before returning, so
        callers always see the logical B.
        """
        if policy == "oracle":
            if oracle_backend == "auto":
                oracle_backend = ("pallas" if self.sweep_scalars is not None
                                  and jax.default_backend() == "tpu"
                                  else "lax")
            act = None  # batched below; the sweep kernel wants whole-grid args
        else:
            act = POLICIES[policy] if isinstance(policy, str) else policy
        params = self._run_params
        b, b_run, gs = self.b, self.b_run, self.gridshard

        def rollout(key):
            key, k0 = jax.random.split(key)
            states = self.reset(k0)

            def body(carry, _):
                # named so profiler dumps attribute per-slot cost to the
                # grid scan (pairs with the host "grid_rollout" span)
                with jax.named_scope("repro.grid_scan_step"):
                    sts, k = carry
                    k, k_act = jax.random.split(k)
                    if act is None:
                        cuts = self.oracle_cuts(sts, backend=oracle_backend)
                    else:
                        cuts = jax.vmap(act)(
                            params, sts,
                            gridshard.cell_keys(k_act, b, b_run))
                    sts2, res = jax.vmap(step_p)(params, sts, cuts)
                    if gs is not None:
                        sts2 = gridshard.constrain(sts2, gs)
                    return (sts2, k), res

            (states, _), results = jax.lax.scan(
                body, (states, key), None, length=steps)
            summary = {
                "reward": jnp.mean(results.reward, axis=0),       # (B,)
                "delay": jnp.mean(results.delay, axis=(0, 2)),
                "energy": jnp.mean(results.energy, axis=(0, 2)),
                "mem": jnp.mean(results.mem_cost, axis=(0, 2)),
                "q_energy_final": jnp.mean(results.q_energy[-1], axis=-1),
                "q_memory_final": jnp.mean(results.q_memory[-1], axis=-1),
                "cut_mean": jnp.mean(results.cut.astype(jnp.float32),
                                     axis=(0, 2)),
            }
            if gs is not None:
                # Padded cells must not pollute anything the caller sees.
                # All summary reductions above are per-cell, so applying the
                # validity mask IS the [:b] slice (gs.mask() stays available
                # for callers doing their own cross-cell aggregation on
                # padded arrays).
                summary = {name: v[:b] for name, v in summary.items()}
                results = gridshard.unpad(results, gs, lead=1)
                states = gridshard.unpad(states, gs)
            return states, results, summary

        return jax.jit(rollout)

    def rollout(self, policy: str | Callable = "oracle", steps: int = 200,
                seed: int = 0, oracle_backend: str = "auto",
                telemetry=None):
        """Convenience one-shot: build + run the jitted rollout.

        ``telemetry=`` (a :class:`repro.obs.Telemetry`) wraps the run in a
        ``grid_rollout`` span and records throughput gauges --
        ``grid_slots_per_s`` (one slot = one (cell, time-slot) advance of
        all N UEs, the benchmarks/scenario_grid.py unit) and
        ``grid_cells_per_s`` -- from one host-side ``block_until_ready``
        timing around the whole program (no extra syncs inside the scan).
        """
        fn = self.make_rollout(policy, steps, oracle_backend=oracle_backend)
        if telemetry is None:
            return fn(jax.random.PRNGKey(seed))
        import time
        m = telemetry.metrics
        with telemetry.tracer.span("grid_rollout", device=True,
                                   cells=self.b, steps=steps):
            t0 = time.perf_counter()
            out = jax.block_until_ready(fn(jax.random.PRNGKey(seed)))
            dt = time.perf_counter() - t0
        m.counter("grid_rollouts_total", "jitted grid rollouts run").inc()
        m.gauge("grid_slots_per_s", "cell x time-slot advances per second "
                "(all N UEs), last rollout").set(self.b * steps / dt)
        m.gauge("grid_cells_per_s", "whole-episode cell throughput, last "
                "rollout").set(self.b / dt)
        return out


def grid_from_names(specs: Sequence[str | tuple[str, dict]]) -> ScenarioGrid:
    """Build a grid from registry names, e.g. ``[("fixed_rate", {"rate": r})
    for r in (0.5, 1.0, 1.5, 2.0, 2.5)]`` evaluates a whole Fig. 4 sweep in
    one program."""
    cells = []
    for spec in specs:
        if isinstance(spec, str):
            cells.append(make(spec))
        else:
            name, knobs = spec
            cells.append(make(name, **knobs))
    return ScenarioGrid(cells)
