"""Pallas TPU kernel: bitmask-tiled SpMV with scalar-prefetched tile walk.

The two-level tiled layout (`docs/ARCHITECTURE.md` §"Bitmask-tiled
layout") streams dense ``(bm, bn)`` tiles with whole-tile FMAs and **no
per-element column indices**: the coarse pointer grid (``tile_ptr``) is
flattened host-side into per-block-row prefetch tables so the BlockSpec
index maps can walk exactly the occupied tiles of each block row —

    y[mb*bm : (mb+1)*bm] += data[tid[mb, k]] @ x[bc[mb, k]*bn : ...]

Empty tiles are never visited (they have no table entry past
``counts[mb]``); partially-occupied tiles are zero-filled so their dead
lanes contribute exact zeros.  This is the cache-blocked answer of
Elafrou et al. applied at the shard level: one ``bc`` id moves a whole
lane-aligned x tile across the memory hierarchy and feeds ``bm*bn``
FMAs, versus one gathered element per FMA for the scalar row formats.

Like ``spmv_bell.py`` before it (this kernel family absorbs Block-ELL),
the tables are *scalar-prefetched* (``PrefetchScalarGridSpec``) so the
index maps run ahead of the compute stream.  The per-tile product is a
VPU multiply and lane reduction in float32 (no MXU pass, so no bf16
rounding of the operands).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .tiling import pad_axis, round_up

__all__ = ["TILES_PER_STEP", "tile_walk_spmv", "tile_contrib"]

#: Tiles per grid step of :func:`tile_contrib`.  The device executor
#: rounds its tile count to a multiple of it, so its operands reach the
#: kernel unpadded.
TILES_PER_STEP = 8


def _tile_spmv_kernel(counts_ref, tid_ref, bc_ref, data_ref, xb_ref, y_ref):
    mb = pl.program_id(0)
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        y_ref[...] = jnp.zeros_like(y_ref)

    # (bm, bn) tile times its (1, bn) x tile, reduced over the lanes.
    contrib = jnp.sum(data_ref[...] * xb_ref[...], axis=1, keepdims=True)
    # Slots past this block row's tile count re-read the last valid tile
    # (the index map clamps); mask their contribution to an exact zero.
    y_ref[...] += jnp.where(k < counts_ref[mb], contrib, 0.0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def tile_walk_spmv(data: jnp.ndarray, counts: jnp.ndarray, tid: jnp.ndarray,
                   bc: jnp.ndarray, x: jnp.ndarray, *,
                   interpret: bool = False) -> jnp.ndarray:
    """y = A @ x over the flattened tile walk (single vector).

    data:   (T, bm, bn) dense zero-filled tiles
    counts: (Mb,) int32 occupied tiles per block row
    tid:    (Mb, K) int32 tile id per walk slot (clamped on padding)
    bc:     (Mb, K) int32 block-column id per walk slot
    x:      (Nb*bn,)  ->  returns y: (Mb*bm,)

    x streams as (Nb, 1, bn) and y as (Mb, bm, 1), so every block's last
    two dimensions equal the array's — the shapes Mosaic accepts for a
    single x tile and a single column of tile rows.  The walk tables are
    prefetched flat: scalar memory pads a 2-D table's last axis to 128,
    and the three tables share its 1 MiB.
    """
    Mb, K = tid.shape
    _, bm, bn = data.shape
    xb = x.reshape(-1, 1, bn)
    return pl.pallas_call(
        _tile_spmv_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(Mb, K),
            in_specs=[
                # Walk the occupied tiles of block row mb, in bc order.
                pl.BlockSpec((None, bm, bn),
                             lambda mb, k, cnt, tid, bc:
                             (tid[mb * K + k], 0, 0)),
                # Stream exactly the x tile this tile multiplies.
                pl.BlockSpec((None, 1, bn),
                             lambda mb, k, cnt, tid, bc:
                             (bc[mb * K + k], 0, 0)),
            ],
            out_specs=pl.BlockSpec((None, bm, 1),
                                   lambda mb, k, cnt, tid, bc: (mb, 0, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((Mb, bm, 1), x.dtype),
        interpret=interpret,
    )(counts, tid.reshape(-1), bc.reshape(-1), data, xb).reshape(Mb * bm)


def _tile_contrib_kernel(d_ref, x_ref, o_ref):
    # (TB, bm, bn) tiles times their (TB, 1, bn) x lanes, lane-reduced
    o_ref[...] = jnp.sum(d_ref[...] * x_ref[...], axis=2, keepdims=True)


@functools.partial(jax.jit, static_argnames=("interpret",))
def tile_contrib(data: jnp.ndarray, xg: jnp.ndarray, *,
                 interpret: bool = False) -> jnp.ndarray:
    """Per-tile dense matvec (T, bm, bn) x (T, bn) -> (T, bm).

    The device executor's flat tile path: x lanes are pre-gathered
    through the remapped augmented buffer (so there is no block grid to
    index), and the dense per-tile FMA stream runs here,
    ``TILES_PER_STEP`` tiles per grid step; the caller scatter-adds the
    contributions into block rows.  A tile count that is not a multiple
    of ``TILES_PER_STEP`` is zero-padded here, which copies the operands:
    the executor's are already aligned.
    """
    T, bm, bn = data.shape
    tb = TILES_PER_STEP
    Tp = round_up(max(T, 1), tb)
    d = pad_axis(data, 0, Tp)
    x3 = pad_axis(xg.astype(data.dtype), 0, Tp).reshape(Tp, 1, bn)
    out = pl.pallas_call(
        _tile_contrib_kernel,
        grid=(Tp // tb,),
        in_specs=[pl.BlockSpec((tb, bm, bn), lambda t: (t, 0, 0)),
                  pl.BlockSpec((tb, 1, bn), lambda t: (t, 0, 0))],
        out_specs=pl.BlockSpec((tb, bm, 1), lambda t: (t, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((Tp, bm, 1), data.dtype),
        interpret=interpret,
    )(d, x3)
    return out[:T, :, 0]
