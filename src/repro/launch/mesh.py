"""Production mesh construction.

Defined as functions (never module-level constants) so importing this
module touches no jax device state — required because the dry-run pins the
device count via XLA_FLAGS before any jax initialisation.
"""

from __future__ import annotations

import jax
import numpy as np


def make_auto_mesh(shape, axes, devices=None):
    """jax.make_mesh with Auto axis types (``jax.make_mesh`` defaults to
    Explicit ones): the model code places arrays with sharding
    constraints and leaves propagation to XLA."""
    return jax.make_mesh(shape, axes, devices=devices,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """(16, 16) = one v5e pod (256 chips) as (data, model);
    (2, 16, 16) = two pods with a leading "pod" DP axis (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = int(np.prod(shape))
    devs = jax.devices()
    if len(devs) < n:
        raise RuntimeError(
            f"mesh {shape} needs {n} devices, found {len(devs)} — the "
            "dry-run entry point must set "
            "XLA_FLAGS=--xla_force_host_platform_device_count=512 before "
            "any jax import")
    return make_auto_mesh(shape, axes, devices=devs[:n])


def make_host_mesh():
    """Whatever this host has (tests / examples): 1×N (data, model)."""
    n = len(jax.devices())
    return make_auto_mesh((n, 1), ("data", "model"))
