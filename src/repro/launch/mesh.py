"""Production meshes.

A function, not a module-level constant: importing this module never touches
jax device state.  The dry-run launcher sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` *before* any jax
import so 512 placeholder host devices exist; real deployments get real TPUs.
"""
from __future__ import annotations

import math

import jax
from jax.sharding import AxisType

__all__ = ["make_production_mesh", "make_mesh"]


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256-chip pod ("data", "model"); 2 pods adds a "pod" DP axis."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape, axes):
    n = math.prod(shape)
    devs = jax.devices()
    if len(devs) < n:
        raise RuntimeError(
            f"need {n} devices for mesh {shape}, have {len(devs)}. For the "
            "dry-run set XLA_FLAGS=--xla_force_host_platform_device_count=512 "
            "before importing jax (launch/dryrun.py does this)."
        )
    return jax.make_mesh(shape, axes, devices=devs[:n],
                         axis_types=(AxisType.Auto,) * len(axes))
