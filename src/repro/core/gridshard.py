"""Device-sharded ScenarioGrid support: mesh placement over the cell axis.

A :class:`~repro.core.scenarios.ScenarioGrid` stacks B cells into one
``(B, ...)`` pytree and evaluates them with one vmap+scan program.  This
module spreads that program across a device mesh's ``"cells"`` axis
(built by :func:`repro.launch.mesh.make_cells_mesh`):

* :func:`plan` rounds B up to a multiple of the mesh's cell-shard count and
  records the split in a :class:`GridSharding`;
* :func:`pad_cells` edge-replicates the last real cell into the padded slots
  (their math stays finite -- no NaNs leak into reductions -- and
  :meth:`GridSharding.mask` marks them invalid so rollout summaries drop
  them);
* :func:`place` / :func:`constrain` put the padded pytree on the mesh with
  ``NamedSharding(P("cells", ...))`` -- under ``jit``, GSPMD then partitions
  the whole vmapped rollout over devices with no per-cell Python dispatch;
* :func:`cell_keys` derives per-cell PRNG keys from the cell *index* (not the
  batch width), so cell i draws identical randomness whether the grid runs
  padded on 8 devices or unpadded on one -- the invariant behind the
  sharded==unsharded parity tests (tests/test_gridshard.py).

On a 2-D ``("cells", "model")`` mesh (``make_cells_mesh(model=M)``) the plan
additionally spreads each cell's *interior* over the ``"model"`` axis: the
dim immediately after the cell axis -- the per-cell UE axis of the stacked
``MecParams``/``MecState`` tables, the row axis of the (B, N, C) objective
sweep -- shards M-way whenever it divides, and replicates otherwise (the
exact-sharding discipline of ``launch.sharding._shard_if``).  Layout only:
pad/mask/place/unpad semantics are unchanged and sharded(cells, model)
rollouts equal unsharded ones to 1e-5 for every registered scenario
(tests/test_gridshard.py's registry-wide parity suite).

Everything here is layout logic only; the per-cell physics stays the pure
``step_p`` / ``reset_p`` of :mod:`repro.core.env`.  That includes per-cell
traffic state riding inside ``MecParams.arrival`` (e.g. a ``(B, T, N)``
stacked trace/regime tensor of :mod:`repro.traffic.processes`): it pads,
places and shards along the same lead cell axis as every other leaf.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

CELL_AXIS = "cells"
MODEL_AXIS = "model"


@dataclasses.dataclass(frozen=True)
class GridSharding:
    """Placement plan for one stacked (B, ...) grid over a device mesh.

    ``b`` logical cells are padded to ``b_padded`` (a multiple of the mesh's
    ``axis`` size) so every device holds the same number of cells.

    ``model_axis`` names the per-cell tensor-parallel mesh axis (present on
    ``("cells", "model")`` meshes): each leaf's first post-cell dim shards
    over it when evenly divisible, giving every cell ``n_model``-way interior
    parallelism on top of the cell split.
    """

    mesh: Mesh
    b: int
    b_padded: int
    axis: str = CELL_AXIS
    model_axis: str | None = None

    def __post_init__(self):
        if self.b_padded < self.b:
            raise ValueError(f"b_padded={self.b_padded} < b={self.b}")
        if self.b_padded % self.n_shards:
            raise ValueError(
                f"b_padded={self.b_padded} not a multiple of the "
                f"{self.n_shards}-way {self.axis!r} axis")
        if self.model_axis is not None \
                and self.model_axis not in self.mesh.axis_names:
            raise ValueError(
                f"mesh has no {self.model_axis!r} axis; axes are "
                f"{self.mesh.axis_names}")

    @property
    def n_shards(self) -> int:
        return int(self.mesh.shape[self.axis])

    @property
    def n_model(self) -> int:
        """Per-cell tensor-parallel degree (1 on a cells-only mesh)."""
        if self.model_axis is None:
            return 1
        return int(self.mesh.shape[self.model_axis])

    @property
    def pad(self) -> int:
        """Number of padded (invalid) trailing cells."""
        return self.b_padded - self.b

    def mask(self) -> jax.Array:
        """(b_padded,) validity mask: True for real cells, False for padding.

        Any reduction that crosses the cell axis (or reports per-cell values
        of a padded rollout) must apply this before trusting the numbers.
        """
        return jnp.arange(self.b_padded) < self.b

    def spec(self, ndim: int, lead: int = 0, shape: tuple | None = None,
             *, model_dim: int | None = None) -> P:
        """PartitionSpec sharding dim ``lead`` over the cells axis.

        Leaves too small to carry a cell axis (0-d scalars riding in a
        pytree) replicate instead of indexing past their rank.

        When the plan carries a ``model_axis`` and the leaf ``shape`` is
        known, one interior dim additionally shards over it: ``lead + 1``
        by default (the per-cell UE axis of stacked MecParams/MecState
        tables), or ``model_dim`` when given (e.g. ``-1`` for arrival
        leaves, whose post-cell dim is a per-slot TIME axis that the hot
        loop indexes every step -- sharding it would gather across shards
        per slot).  Only evenly dividing dims shard (exact shardings,
        never GSPMD padding); everything else replicates across the
        model axis.
        """
        if ndim <= lead:
            return P()
        entries: list = [None] * ndim
        entries[lead] = self.axis
        if (self.model_axis is not None and shape is not None
                and self.n_model > 1):
            md = lead + 1 if model_dim is None else model_dim % ndim
            if md != lead and md < ndim and shape[md] % self.n_model == 0:
                entries[md] = self.model_axis
        return P(*entries)

    def sharding(self, ndim: int, lead: int = 0, shape: tuple | None = None,
                 *, model_dim: int | None = None) -> NamedSharding:
        return NamedSharding(self.mesh,
                             self.spec(ndim, lead, shape,
                                       model_dim=model_dim))


def plan(b: int, mesh: Mesh, *, axis: str = CELL_AXIS,
         pad_to: int | None = None) -> GridSharding:
    """Round ``b`` up to a device multiple and return the placement plan.

    ``pad_to`` forces a larger padded width (it must itself be a device
    multiple) -- used by tests to exercise the padding path on any device
    count, and available for aligning two grids to one layout.

    A mesh carrying a ``"model"`` axis (``make_cells_mesh(model=M)``)
    activates per-cell tensor parallelism: the plan records the axis and
    :meth:`GridSharding.spec` spreads each leaf's post-cell dim over it.
    """
    if axis not in mesh.axis_names:
        raise ValueError(
            f"mesh has no {axis!r} axis; axes are {mesh.axis_names}")
    if b < 1:
        raise ValueError("need at least one cell")
    n = int(mesh.shape[axis])
    b_padded = -(-b // n) * n
    if pad_to is not None:
        if pad_to < b_padded or pad_to % n:
            raise ValueError(
                f"pad_to={pad_to} must be a multiple of {n} and >= {b_padded}")
        b_padded = pad_to
    model_axis = MODEL_AXIS if MODEL_AXIS in mesh.axis_names else None
    return GridSharding(mesh=mesh, b=b, b_padded=b_padded, axis=axis,
                        model_axis=model_axis)


def pad_cells(tree, gs: GridSharding, *, lead: int = 0):
    """Pad every leaf's cell axis from b to b_padded by edge replication.

    Padded cells are copies of the last real cell: every downstream op stays
    finite (unlike zero padding, which would divide by zero in the queueing
    math), and ``gs.mask()`` keeps them out of reported results.
    """
    if gs.pad == 0:
        return tree

    def pad_leaf(x):
        if x.ndim <= lead:           # scalar rider: no cell axis to pad
            return x
        pads = [(0, 0)] * x.ndim
        pads[lead] = (0, gs.pad)
        return jnp.pad(x, pads, mode="edge")

    return jax.tree.map(pad_leaf, tree)


def place(tree, gs: GridSharding, *, lead: int = 0,
          model_dim: int | None = None):
    """``device_put`` every leaf with the cells-axis NamedSharding.

    Leaves must already be padded to ``gs.b_padded`` on axis ``lead``.
    ``model_dim`` overrides which dim takes the model axis (see
    :meth:`GridSharding.spec`).
    """
    return jax.tree.map(
        lambda x: jax.device_put(
            x, gs.sharding(x.ndim, lead, x.shape, model_dim=model_dim)),
        tree)


def constrain(tree, gs: GridSharding, *, lead: int = 0):
    """In-jit ``with_sharding_constraint`` pinning the cell axis.

    Applied to the rollout's state carry so GSPMD keeps the scan partitioned
    over cells instead of gathering between slots.
    """
    def f(x):
        return jax.lax.with_sharding_constraint(
            x, gs.sharding(x.ndim, lead, x.shape))

    return jax.tree.map(f, tree)


def per_shard(fn, gs: GridSharding):
    """``fn`` run once per cell shard: ``shard_map`` over the cells axis,
    every argument and result split on its leading (cell) dim and whole
    across any model axis.  GSPMD cannot partition a Mosaic (Pallas TPU)
    kernel, so a kernel called on the grid's cell-sharded arrays goes
    through here; cells are independent, so each shard's launch is exact.
    """
    spec = P(gs.axis)
    # check_vma off: a pallas_call's out_shape carries no varying-axes
    # annotation for shard_map to check
    return jax.shard_map(fn, mesh=gs.mesh, in_specs=spec, out_specs=spec,
                         check_vma=False)


def unpad(tree, gs: GridSharding, *, lead: int = 0):
    """Slice the cell axis back to the logical b (inverse of pad_cells)."""
    if gs.pad == 0:
        return tree

    def f(x):
        if x.ndim <= lead:           # scalar rider: nothing was padded
            return x
        idx = [slice(None)] * x.ndim
        idx[lead] = slice(0, gs.b)
        return x[tuple(idx)]

    return jax.tree.map(f, tree)


def cell_keys(key: jax.Array, b: int, b_padded: int | None = None):
    """Per-cell PRNG keys: ``fold_in(key, cell_index)``, padded slots clamped.

    Cell i's key depends only on (key, i) -- never on the batch width -- so a
    padded b_padded-wide grid hands cells 0..b-1 exactly the keys an unpadded
    b-wide grid hands them.  That makes sharded and unsharded rollouts draw
    identical randomness per real cell (the 1e-5 parity contract).  Padded
    slots reuse the last real cell's key; their outputs are masked away.
    """
    n = b if b_padded is None else b_padded
    idx = jnp.minimum(jnp.arange(n), b - 1)
    return jax.vmap(lambda i: jax.random.fold_in(key, i))(idx)
