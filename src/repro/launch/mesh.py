"""Production mesh construction.

A FUNCTION, not a module-level constant — importing this module never touches
jax device state (the dry-run must set XLA_FLAGS before the first jax call).
"""

from __future__ import annotations

import math

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 single-pod (256 chips) or 2×16×16 multi-pod (512 chips) mesh."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    need = math.prod(shape)
    devices = jax.devices()
    if len(devices) < need:
        raise RuntimeError(
            f"mesh {shape} needs {need} devices, found {len(devices)} — "
            "set XLA_FLAGS=--xla_force_host_platform_device_count=512 before "
            "any jax import (see launch/dryrun.py)"
        )
    # Auto axes: the model code places activations with
    # with_sharding_constraint, which explicit-axis meshes reject.
    return jax.make_mesh(
        shape, axes, devices=devices[:need], axis_types=(AxisType.Auto,) * len(axes)
    )


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over the real local devices (tests, examples)."""
    n = data * model
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(f"need {n} devices, have {len(devices)}")
    return jax.make_mesh(
        (data, model),
        ("data", "model"),
        devices=devices[:n],
        axis_types=(AxisType.Auto,) * 2,
    )
