"""Production mesh topology.

A function, not a module-level constant: importing this module never touches
jax device state.  Single pod = 16x16 = 256 chips (v5e pod slice); multi-pod
adds a leading 2-wide "pod" axis (512 chips) used for data parallelism with
compressed cross-pod gradient reduction.
"""
from __future__ import annotations

import jax

__all__ = ["make_mesh", "make_production_mesh"]


def make_mesh(shape, axes, devices=None):
    """``jax.make_mesh`` with Auto axis types (over ``devices`` if given)."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)
