"""Block-shape helpers shared by the Pallas kernels.

Mosaic (the TPU Pallas compiler) accepts a block whose last two
dimensions are multiples of the (8, 128) float32 vector tile, or equal
to the whole array's.  Each kernel wrapper pads its operands to those
multiples and picks its block extents with :func:`fit_tile`, so every
shape a caller passes lowers on the chip, not only in interpret mode.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.core.sparse_matrix import ELL_LANE as LANE, \
    ELL_SUBLANE as SUBLANE

__all__ = ["SUBLANE", "LANE", "round_up", "fit_tile", "pad_axis"]


def round_up(n: int, m: int) -> int:
    return ((n + m - 1) // m) * m


def fit_tile(n: int, cap: int, align: int) -> int:
    """Largest multiple of ``align`` that divides ``n`` and is at most
    ``max(cap, align)``.  ``n`` must itself be a multiple of ``align``."""
    if n % align:
        raise ValueError(f"{n} is not a multiple of {align}")
    t = max((min(cap, n) // align) * align, align)
    while n % t:
        t -= align
    return t


def pad_axis(a, axis: int, size: int):
    """Zero-pad ``a`` along ``axis`` up to ``size`` (no-op when equal)."""
    extra = size - a.shape[axis]
    if extra == 0:
        return a
    pad = [(0, 0)] * a.ndim
    pad[axis] = (0, extra)
    return jnp.pad(a, pad)
