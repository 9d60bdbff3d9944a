"""Shared sharded-leg plumbing for the grid benchmarks.

Both ``scenario_grid.py`` and ``traffic_replay.py`` grow a device-sharded
leg from the same ``--devices N [--model M]`` flags; this module holds the
pieces they share so validation/error text never diverges:

* :func:`validate_mesh_args` -- every ``("cells", "model")`` layout
  precondition checked BEFORE jax initializes (the in-library check in
  ``repro.launch.mesh.make_cells_mesh`` re-validates with the same rules;
  doing it pre-init here keeps the message clear of any XLA state).
* :func:`force_devices` -- the ``XLA_FLAGS`` host-device forcing, which
  must land before the first jax array op.
* :func:`leg_tag` -- the ``@8dev`` / ``@4x2dev`` CSV-row suffix.
* :func:`backend_ready` -- False when something initialized the backend
  before the flag landed (the benchmark then exits non-zero instead of
  reporting a leg it did not run).
"""
from __future__ import annotations

import os


def validate_mesh_args(devices: int, model: int) -> str | None:
    """Return an error string for impossible ``--devices/--model`` combos
    (None when valid).  Mirrors ``make_cells_mesh``'s rules."""
    if model < 1:
        return f"--model {model} must be >= 1"
    if model > 1 and not devices:
        return (f"--model {model} needs --devices N (the ('cells','model') "
                "mesh is built over forced host devices)")
    if devices and devices % model:
        return (f"--model {model} does not divide --devices {devices}; "
                "pick a model-axis size from the divisors of "
                f"{devices}")
    return None


def force_devices(devices: int) -> None:
    """Append the host-device forcing flag; call before any jax array op."""
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={devices}")


def leg_tag(devices: int, model: int) -> str:
    """CSV-row suffix naming the device grid: ``@8dev`` or ``@4x2dev``."""
    if model == 1:
        return f"@{devices}dev"
    return f"@{devices // model}x{model}dev"


def backend_ready(devices: int) -> bool:
    """True when the forced device count actually materialized."""
    import jax
    return len(jax.devices()) >= devices
