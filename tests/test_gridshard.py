"""Device-sharded ScenarioGrid (repro.core.gridshard): placement, padding
mask, and sharded-vs-unsharded rollout parity.

Tier-1 runs these on one device (padding forced via ``pad_to``); CI adds a
forced-multi-device CPU leg (``XLA_FLAGS=--xla_force_host_platform_
device_count=8``) where the same tests exercise real 8-way partitioning --
including an uneven B=6 grid and, through the ``model`` parametrizations,
the 2-D ``("cells", "model")`` mesh with per-cell tensor parallelism
(``model ∈ {1, 2, 4}``; degrees not dividing the device count skip).

The parity suite iterates the ENTIRE scenario registry: a newly registered
scenario is covered automatically (every constructor must build with zero
args -- see docs/scenarios.md), at an uneven B so the padding path always
runs.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding

from _hypothesis_compat import given, settings, st

from repro.core import gridshard
from repro.core import scenarios as sc
from repro.core.lymdo import eval_policy_batched, run_fixed_batched
from repro.launch.mesh import make_cells_mesh

N_DEV = len(jax.devices())

# Per-cell tensor-parallel degrees; a degree that does not divide the live
# device count cannot build its ("cells", "model") mesh and skips (tier-1's
# single device runs model=1 only).  The CI forced-8-device matrix narrows
# each leg to ONE degree via REPRO_MODEL_DEGREES so the legs split the work
# instead of triple-running it.
MODEL_DEGREES = [
    pytest.param(m, marks=pytest.mark.skipif(
        N_DEV % m != 0, reason=f"model={m} needs a device count "
                               f"divisible by it (have {N_DEV})"))
    for m in (int(x) for x in
              os.environ.get("REPRO_MODEL_DEGREES", "1,2,4").split(","))
]


def _forced_pad_to(b: int, shards: int = N_DEV) -> int | None:
    """Padded width that guarantees pad > 0 on any cell-shard count."""
    natural = -(-b // shards) * shards
    return natural + shards if natural == b else None


# ---------------------------------------------------------------------------
# Plan / pad / mask units
# ---------------------------------------------------------------------------

def test_plan_rounds_up_to_device_multiple():
    mesh = make_cells_mesh()
    gs = gridshard.plan(3 * N_DEV, mesh)
    assert gs.b_padded == 3 * N_DEV and gs.pad == 0
    gs = gridshard.plan(3 * N_DEV + 1, mesh)
    assert gs.b_padded == 4 * N_DEV
    assert gs.pad == N_DEV - 1
    assert gs.b_padded % gs.n_shards == 0


def test_plan_validates():
    mesh = make_cells_mesh()
    with pytest.raises(ValueError):
        gridshard.plan(2, mesh, axis="nope")
    with pytest.raises(ValueError):
        gridshard.plan(0, mesh)
    with pytest.raises(ValueError):           # pad_to below the natural width
        gridshard.plan(2, mesh, pad_to=1)
    with pytest.raises(ValueError):           # b_padded < b
        gridshard.GridSharding(mesh=mesh, b=2 * N_DEV, b_padded=N_DEV)


def test_pad_unpad_roundtrip_and_mask():
    mesh = make_cells_mesh()
    b_padded = (-(-3 // N_DEV) + 1) * N_DEV   # one extra shard of padding
    gs = gridshard.GridSharding(mesh=mesh, b=3, b_padded=b_padded)
    pad = gs.pad
    assert pad > 0
    tree = {"a": jnp.arange(6.0).reshape(3, 2), "b": jnp.arange(3)}
    padded = gridshard.pad_cells(tree, gs)
    assert padded["a"].shape == (b_padded, 2)
    # edge replication: padded cells copy the last real cell
    np.testing.assert_array_equal(np.asarray(padded["a"][3:]),
                                  np.tile(np.asarray(tree["a"][2:]),
                                          (pad, 1)))
    back = gridshard.unpad(padded, gs)
    for k in tree:
        np.testing.assert_array_equal(np.asarray(back[k]),
                                      np.asarray(tree[k]))
    mask = np.asarray(gs.mask())
    assert mask.shape == (b_padded,)
    assert mask[:3].all() and not mask[3:].any()


def test_scalar_leaves_replicate_through_pad_place_unpad():
    """0-d riders in a pytree must not crash the layout helpers (the same
    bug class batch_shardings had): they replicate and pass through."""
    mesh = make_cells_mesh()
    gs = gridshard.GridSharding(mesh=mesh, b=2, b_padded=2 * N_DEV)
    assert gs.spec(0) == gridshard.P()
    tree = {"vec": jnp.arange(2.0), "scalar": jnp.float32(3.5)}
    padded = gridshard.pad_cells(tree, gs)
    assert padded["scalar"].ndim == 0
    placed = gridshard.place(padded, gs)
    assert placed["scalar"].sharding.spec == ()
    constrained = gridshard.constrain(placed, gs)
    back = gridshard.unpad(constrained, gs)
    assert float(back["scalar"]) == 3.5
    np.testing.assert_array_equal(np.asarray(back["vec"]),
                                  np.asarray(tree["vec"]))


def test_cell_keys_independent_of_padding():
    key = jax.random.PRNGKey(7)
    k_plain = jax.random.key_data(gridshard.cell_keys(key, 5))
    k_pad = jax.random.key_data(gridshard.cell_keys(key, 5, 5 + N_DEV))
    np.testing.assert_array_equal(np.asarray(k_pad[:5]), np.asarray(k_plain))
    # padded slots clamp to the last real cell's key
    np.testing.assert_array_equal(
        np.asarray(k_pad[5:]), np.tile(np.asarray(k_plain[4:5]), (N_DEV, 1)))


# ---------------------------------------------------------------------------
# Grid placement
# ---------------------------------------------------------------------------

def _grid_pair(b: int, pad_to=None, ues: int = 3, seed: int = 5):
    cells = sc.multicell_grid(cells=b, ues=ues, seed=seed)
    plain = sc.ScenarioGrid(cells)
    shard = sc.ScenarioGrid(cells).use_mesh(make_cells_mesh(), pad_to=pad_to)
    return plain, shard


def test_use_mesh_places_params_on_cells_axis():
    _, g = _grid_pair(3, pad_to=_forced_pad_to(3))
    gs = g.gridshard
    assert gs is not None and g.b_run == gs.b_padded >= g.b
    for leaf in jax.tree.leaves(g._run_params):
        assert leaf.shape[0] == g.b_run
        assert isinstance(leaf.sharding, NamedSharding)
        assert leaf.sharding.spec[0] == "cells"
    # the logical stack is untouched
    assert g.params.L.shape[0] == g.b


def test_params_for_rejects_unknown_width():
    _, g = _grid_pair(3, pad_to=_forced_pad_to(3))
    states = g.reset(jax.random.PRNGKey(0))
    assert states.t.shape[0] == g.b_run
    bad = jax.tree.map(lambda x: jnp.concatenate([x, x]), states)
    with pytest.raises(ValueError):
        g.step(bad, jnp.zeros((2 * g.b_run, g.n_ue), jnp.int32))


def test_objective_tables_on_padded_states():
    _, g = _grid_pair(4, pad_to=_forced_pad_to(4))
    states = g.reset(jax.random.PRNGKey(1))
    table = np.asarray(g.objective_tables(states, backend="lax"))
    assert table.shape == (g.b_run, g.n_ue, g.num_cuts)
    assert np.isfinite(table[table < 1e29]).all()


# ---------------------------------------------------------------------------
# Sharded == unsharded parity (the 1e-5 contract)
# ---------------------------------------------------------------------------

def _assert_parity(b: int, pad_to, policy: str, steps: int = 12,
                   oracle_backend: str = "auto"):
    g_plain, g_shard = _grid_pair(b, pad_to=pad_to)
    st_p, res_p, sum_p = g_plain.rollout(policy, steps=steps, seed=3,
                                         oracle_backend=oracle_backend)
    st_s, res_s, sum_s = g_shard.rollout(policy, steps=steps, seed=3,
                                         oracle_backend=oracle_backend)
    assert set(sum_p) == set(sum_s)
    for name in sum_p:
        assert np.asarray(sum_s[name]).shape == (b,)
        np.testing.assert_allclose(np.asarray(sum_s[name]),
                                   np.asarray(sum_p[name]),
                                   rtol=1e-5, atol=1e-7, err_msg=name)
    for got, want in zip(jax.tree.leaves(res_s), jax.tree.leaves(res_p)):
        assert got.shape == want.shape     # logical B, padding sliced off
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-7)
    for got, want in zip(jax.tree.leaves(st_s), jax.tree.leaves(st_p)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-7)
    return g_shard


def test_sharded_parity_even_b():
    b = max(2, N_DEV)                     # a device multiple: no padding
    g = _assert_parity(b, None, "oracle")
    assert g.gridshard.pad == 0


def test_sharded_parity_uneven_b_exercises_padding():
    b = 6                                 # uneven on 8 (and forced elsewhere)
    g = _assert_parity(b, _forced_pad_to(b), "oracle")
    assert g.gridshard.pad > 0


def test_sharded_parity_pallas_sweep():
    """The Oracle's Pallas sweep runs once per cell shard (GSPMD cannot
    partition a Mosaic kernel): still equal to the unsharded rollout."""
    b = 5
    g = _assert_parity(b, _forced_pad_to(b), "oracle", steps=6,
                       oracle_backend="pallas")
    assert g.gridshard.pad > 0


def test_sharded_parity_random_policy():
    b = 5
    g = _assert_parity(b, _forced_pad_to(b), "random")
    assert g.gridshard.pad > 0


# ---------------------------------------------------------------------------
# Mesh construction validates up front (no opaque XLA errors)
# ---------------------------------------------------------------------------

def test_make_cells_mesh_validates_device_count():
    with pytest.raises(ValueError, match="force_host_platform_device_count"):
        make_cells_mesh(2 * N_DEV)
    with pytest.raises(ValueError, match="at least one device"):
        make_cells_mesh(0)


def test_make_cells_mesh_validates_model_axis():
    with pytest.raises(ValueError, match="does not divide"):
        make_cells_mesh(N_DEV, model=3 * N_DEV)
    with pytest.raises(ValueError, match="model axis size"):
        make_cells_mesh(N_DEV, model=0)


def test_use_mesh_rejects_model_mesh_mismatch():
    cells = sc.multicell_grid(cells=2, ues=3, seed=0)
    with pytest.raises(ValueError, match="model"):
        sc.ScenarioGrid(cells).use_mesh(make_cells_mesh(), model=2 * N_DEV)


@pytest.mark.parametrize("model", MODEL_DEGREES)
def test_use_mesh_model_places_2d(model):
    """use_mesh(model=M) builds the ("cells","model") mesh itself and the
    plan records the per-cell TP degree; params leaves whose post-cell dim
    divides M shard over the model axis, the rest replicate across it."""
    cells = sc.multicell_grid(cells=3, ues=4, seed=5)
    g = sc.ScenarioGrid(cells).use_mesh(model=model)
    gs = g.gridshard
    assert gs.n_model == model
    assert gs.n_shards == N_DEV // model
    for leaf in jax.tree.leaves(g._run_params):
        assert leaf.shape[0] == g.b_run
        spec = leaf.sharding.spec
        assert spec[0] == "cells"
        if model > 1 and leaf.ndim > 1 and leaf.shape[1] % model == 0:
            assert spec[1] == "model", leaf.shape
    if model > 1:
        # the N=4 UE axis divides every tested degree: TP is actually on
        n_specs = [leaf.sharding.spec for leaf in
                   jax.tree.leaves(g._run_params) if leaf.ndim > 1]
        assert any(s[1] == "model" for s in n_specs)


# ---------------------------------------------------------------------------
# Registry-wide sharded parity: EVERY registered scenario, any model degree
# ---------------------------------------------------------------------------

_REG_STEPS = 6
_REG_B = 3                     # uneven on most shard counts -> padding runs
_plain_summaries: dict = {}


def _registry_cells(name: str):
    """B zero-arg cells of one registered scenario (per-cell randomness
    still differs through the grid's fold_in key discipline)."""
    return [sc.make(name) for _ in range(_REG_B)]


def _plain_summary(name: str):
    if name not in _plain_summaries:
        g = sc.ScenarioGrid(_registry_cells(name))
        _, _, summary = g.rollout("oracle", steps=_REG_STEPS, seed=3)
        _plain_summaries[name] = {k: np.asarray(v)
                                  for k, v in summary.items()}
    return _plain_summaries[name]


@pytest.mark.parametrize("model", MODEL_DEGREES)
@pytest.mark.parametrize("name", sc.names())
def test_registry_sharded_parity(name, model):
    """sharded(cells, model) == unsharded to 1e-5 for every registered
    scenario, uneven-B padding included -- the headline model-axis
    guarantee.  Registering a new scenario extends this suite for free."""
    mesh = make_cells_mesh(model=model)
    shards = N_DEV // model
    g = sc.ScenarioGrid(_registry_cells(name)).use_mesh(
        mesh, pad_to=_forced_pad_to(_REG_B, shards))
    assert g.gridshard.pad > 0          # the padding path always exercised
    assert g.gridshard.n_model == model
    _, _, summary = g.rollout("oracle", steps=_REG_STEPS, seed=3)
    want = _plain_summary(name)
    assert set(summary) == set(want)
    for key in want:
        got = np.asarray(summary[key])
        assert got.shape == (_REG_B,)
        np.testing.assert_allclose(got, want[key], rtol=1e-5, atol=1e-7,
                                   err_msg=f"{name}[{key}] model={model}")


def test_registry_constructors_build_with_zero_args():
    """The contract the registry-wide suite relies on: every registered
    constructor yields a Scenario with no arguments."""
    for name in sc.names():
        cell = sc.make(name)
        assert isinstance(cell, sc.Scenario), name
        assert cell.n_ue >= 1, name


# ---------------------------------------------------------------------------
# Layout round-trip property (hypothesis; fixed-examples shim on bare envs)
# ---------------------------------------------------------------------------

class TestLayoutRoundTrip:
    """pad_cells -> place -> unpad is the identity and the validity mask is
    padding-invariant, for arbitrary (B, cells, model) leaf shapes."""

    @pytest.mark.parametrize("model", MODEL_DEGREES)
    @given(b=st.integers(1, 9), extra=st.integers(0, 2),
           k=st.integers(1, 6))
    @settings(max_examples=12, deadline=None)
    def test_pad_place_unpad_identity(self, model, b, extra, k):
        mesh = make_cells_mesh(model=model)
        shards = N_DEV // model
        natural = -(-b // shards) * shards
        gs = gridshard.plan(b, mesh, pad_to=natural + extra * shards)
        rng = np.random.default_rng(b * 100 + extra * 10 + k)
        tree = {
            "vec": jnp.asarray(rng.normal(size=(b,)).astype(np.float32)),
            "mat": jnp.asarray(rng.normal(size=(b, k)).astype(np.float32)),
            "cube": jnp.asarray(
                rng.normal(size=(b, k, 3)).astype(np.float32)),
            "scalar": jnp.float32(1.5),
        }
        placed = gridshard.place(gridshard.pad_cells(tree, gs), gs)
        for key, leaf in placed.items():
            if leaf.ndim:
                assert leaf.shape[0] == gs.b_padded, key
        back = gridshard.unpad(placed, gs)
        for key in tree:
            np.testing.assert_array_equal(np.asarray(back[key]),
                                          np.asarray(tree[key]), err_msg=key)
        mask = np.asarray(gs.mask())
        assert mask.sum() == b and mask[:b].all()

    @pytest.mark.parametrize("model", MODEL_DEGREES)
    @given(b=st.integers(1, 6), extra=st.integers(1, 3))
    @settings(max_examples=8, deadline=None)
    def test_mask_is_padding_invariant(self, model, b, extra):
        """The first b mask entries are True at ANY padded width: widening
        the pad never flips a real cell's validity."""
        mesh = make_cells_mesh(model=model)
        shards = N_DEV // model
        natural = -(-b // shards) * shards
        narrow = gridshard.plan(b, mesh)
        wide = gridshard.plan(b, mesh, pad_to=natural + extra * shards)
        m_n, m_w = np.asarray(narrow.mask()), np.asarray(wide.mask())
        np.testing.assert_array_equal(m_w[:len(m_n)][:b], m_n[:b])
        assert m_n.sum() == m_w.sum() == b
        assert not m_w[b:].any()


# ---------------------------------------------------------------------------
# Batched runners accept the sharded path transparently
# ---------------------------------------------------------------------------

def test_run_fixed_batched_transparent():
    g_plain, g_shard = _grid_pair(3, pad_to=_forced_pad_to(3))
    m_p, r_p = run_fixed_batched(g_plain, "local", episodes=2, steps=8,
                                 seed=11)
    m_s, r_s = run_fixed_batched(g_shard, "local", episodes=2, steps=8,
                                 seed=11)
    for name in m_p:
        assert m_s[name].shape == (g_plain.b,)
        np.testing.assert_allclose(m_s[name], m_p[name],
                                   rtol=1e-5, atol=1e-7, err_msg=name)
    np.testing.assert_allclose(np.asarray(r_s.delay), np.asarray(r_p.delay),
                               rtol=1e-5, atol=1e-7)


def test_eval_policy_batched_transparent():
    from repro.core.policies import GaussianTanhPolicy
    from repro.core.ppo import PPO, PPOConfig

    rates = (1.0, 1.5, 2.0)
    g_plain = sc.grid_from_names([("fixed_rate", {"rate": r})
                                  for r in rates])
    g_shard = sc.grid_from_names([("fixed_rate", {"rate": r})
                                  for r in rates])
    g_shard.use_mesh(make_cells_mesh(), pad_to=_forced_pad_to(g_shard.b))
    env = g_plain.scenarios[0].build()
    pol = GaussianTanhPolicy(env.obs_dim, env.L)
    agent = PPO(pol, env.obs_dim, PPOConfig())
    state = agent.init(jax.random.PRNGKey(0))
    m_p, _ = eval_policy_batched(g_plain, agent, state, episodes=1, steps=6)
    m_s, _ = eval_policy_batched(g_shard, agent, state, episodes=1, steps=6)
    for name in m_p:
        np.testing.assert_allclose(m_s[name], m_p[name],
                                   rtol=1e-5, atol=1e-7, err_msg=name)
