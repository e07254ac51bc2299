"""Pallas TPU kernels: split-nnz two-stage SpMV (split-K).

``spmv_seg`` cures row skew at chunk granularity, but its grid is the
chunk count: a shard that is one monster row lowers to a handful of
chunks and leaves the machine idle — the paper's §IV-D hot-spot
reappears one level up.  This is the split-K decode idiom (aiter MLA,
SNIPPETS.md §2) ported to SpMV:

* stage 1 (``split_psum``): the (C, L) nnz slab is reshaped to
  (NS, Cs, L) and a 2-D grid ``(NS, Cs // tc)`` computes within-chunk
  inclusive prefix sums per split — NS independent partial accumulators,
  so even a one-row shard fills ``NS * Cs/tc`` grid steps;
* the carry fix-up scatters each split's pieces into a *partial* row-sum
  buffer (NS, R) (cheap jit'd gather/scatter in ops, same shape as the
  seg fix-up but indexed by split);
* stage 2 (``split_combine``): a tiny reduction over the split axis,
  (NS, R) -> (R,) — the aiter ``_fwd_kernel_stage2`` analogue.

The split count NS is a planning decision (``plan.split_meta``), driven
by the row span (chunks of the longest row) and the device core count —
the ``get_meta_param`` analogue.  As in ``spmv_seg``, the gather of x
runs in XLA before stage 1 and the scan is :func:`~.spmv_seg.lane_scan`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .spmv_seg import lane_scan
from .tiling import LANE, SUBLANE, fit_tile, pad_axis, round_up

__all__ = ["split_psum", "split_combine"]


def _split_psum_kernel(vals_ref, xg_ref, psum_ref):
    psum_ref[...] = lane_scan(vals_ref[...] * xg_ref[...])   # (TC, L)


@functools.partial(jax.jit, static_argnames=("tile_c", "interpret"))
def split_psum(vals: jnp.ndarray, cols: jnp.ndarray, x: jnp.ndarray,
               *, tile_c: int = 8, interpret: bool = False) -> jnp.ndarray:
    """Stage 1: per-chunk inclusive prefix sums over a split slab.

    vals/cols: (NS, Cs, L) nnz-stream slab with L % 128 == 0.  The grid
    is 2-D, (NS, Cs // tc): the split axis keeps every core busy even
    when Cs is tiny (one monster row => C chunks cut into NS splits).
    Cs is padded to a multiple of 8 for the tile.  x: (N,), gathered in
    HBM.  Returns psum: (NS, Cs, L) in x.dtype.
    """
    NS, Cs, L = vals.shape
    if L % LANE:
        raise ValueError(f"chunk length {L} is not a multiple of {LANE}")
    Cp = round_up(max(Cs, 1), SUBLANE)
    tc = fit_tile(Cp, tile_c, SUBLANE)
    xg = jnp.take(x, cols, axis=0).astype(x.dtype)         # XLA gather
    v = pad_axis(vals.astype(x.dtype), 1, Cp)
    xg = pad_axis(xg, 1, Cp)
    psum = pl.pallas_call(
        _split_psum_kernel,
        grid=(NS, Cp // tc),
        in_specs=[
            pl.BlockSpec((None, tc, L), lambda s, c: (s, c, 0)),  # vals
            pl.BlockSpec((None, tc, L), lambda s, c: (s, c, 0)),  # x[cols]
        ],
        out_specs=pl.BlockSpec((None, tc, L), lambda s, c: (s, c, 0)),
        out_shape=jax.ShapeDtypeStruct((NS, Cp, L), x.dtype),
        interpret=interpret,
    )(v, xg)
    return psum[:, :Cs]


def _split_combine_kernel(part_ref, y_ref):
    y_ref[...] = jnp.sum(part_ref[...], axis=0, keepdims=True)  # (1, TR)


@functools.partial(jax.jit, static_argnames=("tile_r", "interpret"))
def split_combine(partial: jnp.ndarray, *, tile_r: int = 512,
                  interpret: bool = False) -> jnp.ndarray:
    """Stage 2: reduce the per-split partial row sums, (NS, R) -> (R,).

    R is padded to a multiple of 128 and tiled by the largest multiple of
    128 dividing it, not above ``max(tile_r, 128)``."""
    NS, R = partial.shape
    Rp = round_up(max(R, 1), LANE)
    tr = fit_tile(Rp, tile_r, LANE)
    y = pl.pallas_call(
        _split_combine_kernel,
        grid=(Rp // tr,),
        in_specs=[pl.BlockSpec((NS, tr), lambda r: (0, r))],
        out_specs=pl.BlockSpec((1, tr), lambda r: (0, r)),
        out_shape=jax.ShapeDtypeStruct((1, Rp), partial.dtype),
        interpret=interpret,
    )(pad_axis(partial, 1, Rp))
    return y[0, :R]
