"""Pallas TPU kernel: ELL-format SpMV.

TPU adaptation of the paper's CSR row loop (docs/ARCHITECTURE.md#design-2):
a scalar
CSR walk cannot feed the VPU, so rows are padded to a lane-aligned width W
and the kernel processes (TM, TW) tiles of the ELL slab:

    y[i] += sum_w data[i, w] * xg[i, w],    xg[i, w] = x[cols[i, w]]

Grid is (M/TM, W/TW); the W-axis is the reduction, accumulated in the
(TM, 1) output tile (revisited across the w grid dimension, initialised at
w == 0).  Mosaic has no 1-D dynamic gather, so the gather of x through the
column ids runs in XLA before the kernel (the same split as the tile
family's pre-gathered ``xg``); the kernel streams the two aligned slabs and
does the multiply and the lane reduction.  x is the *block-layout local
shard* (plus its halo), so every gather that would have been a migration on
Emu is a local HBM read here — which is why the distributed layer
(core/program.py) reproduces the paper's block-layout win on TPU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .tiling import LANE, SUBLANE, fit_tile, pad_axis, round_up

__all__ = ["ell_spmv"]


def _ell_kernel(data_ref, xg_ref, y_ref):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        y_ref[...] = jnp.zeros_like(y_ref)

    # (TM, TW) products, reduced over the lanes into the (TM, 1) tile
    y_ref[...] += jnp.sum(data_ref[...] * xg_ref[...], axis=1, keepdims=True)


@functools.partial(jax.jit, static_argnames=("tile_m", "tile_w", "interpret"))
def ell_spmv(data: jnp.ndarray, cols: jnp.ndarray, x: jnp.ndarray,
             *, tile_m: int = 256, tile_w: int = 512,
             interpret: bool = False) -> jnp.ndarray:
    """y = A @ x with A in padded-ELL form.

    data/cols: (M, W); padded slots hold value 0.  The slab is padded to
    (8, 128) multiples and tiled by the largest aligned divisors not above
    ``tile_m`` / ``tile_w``.  x: (N,), any length — it is gathered in HBM,
    never held whole in VMEM.  Returns y: (M,) in x.dtype.
    """
    M, W = data.shape
    Mp, Wp = round_up(max(M, 1), SUBLANE), round_up(max(W, 1), LANE)
    tm = fit_tile(Mp, tile_m, SUBLANE)
    tw = fit_tile(Wp, tile_w, LANE)
    xg = jnp.take(x, cols, axis=0).astype(x.dtype)         # XLA gather
    d = pad_axis(pad_axis(data.astype(x.dtype), 0, Mp), 1, Wp)
    xg = pad_axis(pad_axis(xg, 0, Mp), 1, Wp)
    y = pl.pallas_call(
        _ell_kernel,
        grid=(Mp // tm, Wp // tw),
        in_specs=[
            pl.BlockSpec((tm, tw), lambda m, w: (m, w)),       # data tile
            pl.BlockSpec((tm, tw), lambda m, w: (m, w)),       # x[cols] tile
        ],
        out_specs=pl.BlockSpec((tm, 1), lambda m, w: (m, 0)),
        out_shape=jax.ShapeDtypeStruct((Mp, 1), x.dtype),
        interpret=interpret,
    )(d, xg)
    return y[:M, 0]
