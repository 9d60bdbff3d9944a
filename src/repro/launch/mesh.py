"""Production mesh construction (MULTI-POD DRY-RUN spec).

A FUNCTION, not a module-level constant: importing this module never touches
jax device state (device count locks on first jax init).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def auto_mesh(shape, axes, devices=None):
    """The one mesh constructor: every axis is Auto.  ``jax.make_mesh``
    defaults to Explicit axes, which ``shardctx.constrain``'s
    ``with_sharding_constraint`` and the grid's ``NamedSharding`` placement
    refuse."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips/pod; multi-pod adds a leading 2-pod axis (512).
    Only the compile-only dry run (launch/dryrun.py) builds it."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def make_host_mesh():
    """Degenerate 1x1 mesh for CPU smoke runs of the same launch code."""
    return auto_mesh((1, 1), ("data", "model"))


def make_live_mesh(model: int | None = None):
    """``(data=1, model=M)`` over the live devices, for the serve and train
    launchers: M defaults to the device count, so one chip runs unsharded
    and four shard heads/FFN 4 ways."""
    n = len(jax.devices())
    model = n if model is None else int(model)
    if not 1 <= model <= n:
        raise ValueError(f"model axis {model} needs 1..{n} live devices")
    return auto_mesh((1, model), ("data", "model"),
                     devices=jax.devices()[:model])


def data_axes(mesh) -> tuple:
    """Mesh axes that carry the batch (DP): ("pod","data") when present."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def make_cells_mesh(n_devices: int | None = None, *, model: int = 1):
    """Mesh for sharding a ScenarioGrid's stacked cell axis (see
    repro.core.gridshard): 1-D ``("cells",)``, or 2-D ``("cells", "model")``
    when ``model > 1`` -- the trailing axis carries per-cell tensor
    parallelism (grid tables shard their post-cell dim, served LM weights
    shard their head/FFN dims via ``launch.sharding.param_spec``).

    ``n_devices=None`` uses every live device (on CPU, force several with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` *before* jax
    initializes).  With ``model > 1`` cells get ``n_devices // model``
    shards.

    Every layout precondition is validated HERE, with an actionable message,
    so callers (benchmarks, tests, grids) never surface an opaque XLA
    device-assignment error.
    """
    avail = len(jax.devices())
    n = avail if n_devices is None else int(n_devices)
    model = int(model)
    if n < 1:
        raise ValueError(f"need at least one device, got n_devices={n}")
    if n > avail:
        raise ValueError(
            f"requested a {n}-device cells mesh but only {avail} device(s) "
            f"are live; on CPU force more with XLA_FLAGS="
            f"--xla_force_host_platform_device_count={n} before jax "
            "initializes (anything that touches jax arrays locks the count)")
    if model < 1:
        raise ValueError(f"model axis size must be >= 1, got model={model}")
    if n % model:
        raise ValueError(
            f"model={model} does not divide the {n}-device mesh; pick a "
            f"model-axis size from the divisors of {n} "
            f"(e.g. {[d for d in (1, 2, 4, 8) if n % d == 0]})")
    if model > 1:
        return auto_mesh((n // model, model), ("cells", "model"),
                         devices=jax.devices()[:n])
    return auto_mesh((n,), ("cells",), devices=jax.devices()[:n])


def elastic_mesh(target_model: int = 16):
    """Elastic variant: builds the largest (data, model) mesh the *live*
    device set supports -- used by the runtime's restart-after-failure path
    (runtime/elastic.py).  model axis shrinks only if devices < target."""
    n = len(jax.devices())
    model = min(target_model, n)
    while n % model:
        model -= 1
    return auto_mesh((n // model, model), ("data", "model"))
