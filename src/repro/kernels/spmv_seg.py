"""Pallas TPU kernel: nonzero-balanced segmented-sum SpMV.

The row-tiled ELL kernel (spmv_ell.py) inherits the paper's §IV-D failure
mode at tile granularity: a power-law row makes its tile's reduction width
explode while every other tile pads.  This kernel is the nonzero-split fix
(merge-path style, cf. Elafrou et al. / Merrill & Garland): the flat nnz
stream is cut into equal-size lane-aligned chunks — every grid step owns
exactly ``chunk`` non-zeros no matter how skewed the rows are — and the
kernel computes, per chunk, the products and their within-chunk inclusive
prefix sums:

    psum[c, l] = sum_{k <= l} vals[c, k] * x[cols[c, k]]

Row results are then assembled by the cross-chunk carry fix-up (a cheap
jit'd gather/scatter in ops.seg_spmv): each (chunk, row) *piece* contributes
``psum[c, hi] - psum[c, lo-1]`` to its row, so a row spanning many chunks
sums one carry per chunk and a chunk holding many short rows yields them
all from one scan.  The grid is therefore load-balance-aware rather than
shape-aware — the first kernel in this repo whose work distribution, not
its operand shape, defines the grid.

Mosaic lowers neither a 1-D dynamic gather nor ``cumsum``: the gather
``x[cols]`` runs in XLA before the kernel, and the scan is a log-step
(Hillis-Steele) sum of lane rotations, :func:`lane_scan`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .tiling import LANE, SUBLANE, fit_tile, pad_axis, round_up

__all__ = ["seg_psum", "lane_scan"]


def lane_scan(v):
    """Inclusive prefix sum along the last (lane) axis, in-kernel.

    ``log2(L)`` steps; step ``s`` adds the value ``s`` lanes to the left
    (a lane rotation, masked where it wraps around)."""
    L = v.shape[-1]
    lane = jax.lax.broadcasted_iota(jnp.int32, v.shape, v.ndim - 1)
    s = 1
    while s < L:
        v = v + jnp.where(lane >= s, pltpu.roll(v, s, v.ndim - 1), 0.0)
        s *= 2
    return v


def _seg_kernel(vals_ref, xg_ref, psum_ref):
    psum_ref[...] = lane_scan(vals_ref[...] * xg_ref[...])


@functools.partial(jax.jit, static_argnames=("tile_c", "interpret"))
def seg_psum(vals: jnp.ndarray, cols: jnp.ndarray, x: jnp.ndarray,
             *, tile_c: int = 8, interpret: bool = False) -> jnp.ndarray:
    """Per-chunk inclusive prefix sums of ``vals * x[cols]``.

    vals/cols: (C, L) nnz-stream slab with L % 128 == 0.  The chunk axis
    is padded to a multiple of 8 and tiled by the largest multiple of 8
    dividing it, not above ``max(tile_c, 8)``.  x: (N,), any length — it
    is gathered in HBM.  Returns psum: (C, L) in x.dtype.
    """
    C, L = vals.shape
    if L % LANE:
        raise ValueError(f"chunk length {L} is not a multiple of {LANE}")
    Cp = round_up(max(C, 1), SUBLANE)
    tc = fit_tile(Cp, tile_c, SUBLANE)
    xg = jnp.take(x, cols, axis=0).astype(x.dtype)         # XLA gather
    v = pad_axis(vals.astype(x.dtype), 0, Cp)
    xg = pad_axis(xg, 0, Cp)
    psum = pl.pallas_call(
        _seg_kernel,
        grid=(Cp // tc,),
        in_specs=[
            pl.BlockSpec((tc, L), lambda c: (c, 0)),           # vals tile
            pl.BlockSpec((tc, L), lambda c: (c, 0)),           # x[cols] tile
        ],
        out_specs=pl.BlockSpec((tc, L), lambda c: (c, 0)),
        out_shape=jax.ShapeDtypeStruct((Cp, L), x.dtype),
        interpret=interpret,
    )(v, xg)
    return psum[:C]
