"""Production mesh construction.

A function (not a module constant) so importing this module never touches
jax device state.  Single pod: 16x16 = 256 chips ("data", "model").
Multi-pod: 2x16x16 = 512 chips ("pod", "data", "model") — the "pod" axis
carries only DP traffic (gradient all-reduce, optionally int8-compressed)
since it maps to the slower inter-pod links.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh(model_parallel: int | None = None):
    """Mesh over whatever devices exist (tests / examples on CPU)."""
    n = len(jax.devices())
    mp = model_parallel or (2 if n % 2 == 0 and n > 1 else 1)
    return jax.make_mesh((n // mp, mp), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
