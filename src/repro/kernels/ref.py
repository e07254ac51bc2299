"""Pure-jnp oracles for every kernel in this package.

These are the correctness references the per-kernel tests sweep against
(shapes x dtypes, assert_allclose).  They are also the fallback execution
path on backends without Pallas support.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

#: f32 products at full precision: the TPU's default matmul precision
#: rounds f32 operands to bf16, which would break the oracle contract.
_EXACT = jax.lax.Precision.HIGHEST

__all__ = ["ell_spmv_ref", "bell_spmv_ref", "coo_spmv_ref", "bell_spmm_ref",
           "seg_spmv_ref", "seg_psum_ref", "split_psum_ref",
           "split_partial_ref", "split_combine_ref", "split_spmv_ref",
           "tile_spmv_ref", "tile_flat_spmv_ref"]


def ell_spmv_ref(data: jnp.ndarray, cols: jnp.ndarray, x: jnp.ndarray) -> jnp.ndarray:
    """y[i] = sum_w data[i, w] * x[cols[i, w]]  — padded slots hold 0.

    ``x`` may be (N,) or a multi-RHS block (N, B); the result matches
    ((M,) or (M, B)).  The batched path reuses the same gather and the
    same axis-1 reduction, so per-column results equal the per-vector
    ones exactly.
    """
    gathered = jnp.take(x, cols, axis=0)     # (M, W) or (M, W, B)
    if x.ndim == 2:
        return jnp.sum(data[..., None] * gathered, axis=1)
    return jnp.sum(data * gathered, axis=1)


def coo_spmv_ref(rows: jnp.ndarray, cols: jnp.ndarray, vals: jnp.ndarray,
                 x: jnp.ndarray, num_rows: int) -> jnp.ndarray:
    """Scatter-add oracle for the HYB overflow tail."""
    contrib = vals * jnp.take(x, cols, axis=0)
    return jnp.zeros((num_rows,), dtype=contrib.dtype).at[rows].add(contrib)


def seg_spmv_ref(vals: jnp.ndarray, cols: jnp.ndarray, rows: jnp.ndarray,
                 x: jnp.ndarray, num_rows: int) -> jnp.ndarray:
    """Segmented SpMV oracle over the chunked nnz stream.

    vals/cols/rows: (C, L) slab (padded slots: val 0 / col 0 / row 0).
    Scatter-adds every product into its destination row — the order-free
    definition the chunked prefix-sum kernel must reproduce.  ``x`` may be
    (N,) or a multi-RHS block (N, B); the (C, L) row ids then scatter
    whole (B,) slices, so batched columns match per-vector runs exactly.
    """
    gathered = jnp.take(x, cols, axis=0)     # (C, L) or (C, L, B)
    if x.ndim == 2:
        contrib = vals[..., None] * gathered
        out = jnp.zeros((num_rows, x.shape[1]), dtype=contrib.dtype)
    else:
        contrib = vals * gathered
        out = jnp.zeros((num_rows,), dtype=contrib.dtype)
    return out.at[rows].add(contrib)


def seg_psum_ref(vals: jnp.ndarray, cols: jnp.ndarray, x: jnp.ndarray) -> jnp.ndarray:
    """Within-chunk inclusive prefix sums — oracle for kernels.spmv_seg."""
    return jnp.cumsum(vals * jnp.take(x, cols, axis=0), axis=1)


def split_psum_ref(vals: jnp.ndarray, cols: jnp.ndarray,
                   x: jnp.ndarray) -> jnp.ndarray:
    """Stage-1 oracle: within-chunk scans over the (NS, Cs, L) slab."""
    return jnp.cumsum(vals * jnp.take(x, cols, axis=0), axis=-1)


def split_partial_ref(psum: jnp.ndarray, piece_split: jnp.ndarray,
                      piece_chunk: jnp.ndarray, piece_lo: jnp.ndarray,
                      piece_hi: jnp.ndarray, piece_row: jnp.ndarray,
                      num_splits: int, num_rows: int) -> jnp.ndarray:
    """Carry fix-up into per-split partial row sums.

    psum: (NS, Cs, L) stage-1 scans (trailing batch dims allowed).  Each
    piece contributes ``psum[s, c, hi] - psum[s, c, lo-1]`` to partial
    row ``(s, row)``; ``lo == 0`` contributes the plain prefix.  Returns
    (NS, num_rows) partials (plus any batch dims).
    """
    hi = psum[piece_split, piece_chunk, piece_hi]
    lo = jnp.where((piece_lo > 0)[(...,) + (None,) * (hi.ndim - 1)],
                   psum[piece_split, piece_chunk,
                        jnp.maximum(piece_lo - 1, 0)], 0)
    contrib = hi - lo
    out = jnp.zeros((num_splits, num_rows) + psum.shape[3:],
                    dtype=psum.dtype)
    return out.at[piece_split, piece_row].add(contrib)


def split_combine_ref(partial: jnp.ndarray) -> jnp.ndarray:
    """Stage-2 oracle: reduce the split axis, (NS, R, ...) -> (R, ...)."""
    return jnp.sum(partial, axis=0)


def split_spmv_ref(vals: jnp.ndarray, cols: jnp.ndarray, rows: jnp.ndarray,
                   x: jnp.ndarray, num_rows: int) -> jnp.ndarray:
    """End-to-end split oracle — identical contract to seg_spmv_ref.

    The split axis only partitions the nnz stream; flattening it back to
    a (NS*Cs, L) slab and scatter-adding gives the order-free answer.
    """
    NS, Cs, L = vals.shape
    return seg_spmv_ref(vals.reshape(NS * Cs, L), cols.reshape(NS * Cs, L),
                        rows.reshape(NS * Cs, L), x, num_rows)


def tile_spmv_ref(data: jnp.ndarray, tile_rows: jnp.ndarray,
                  tile_cols: jnp.ndarray, x: jnp.ndarray,
                  num_rows: int) -> jnp.ndarray:
    """Bitmask-tiled SpMV oracle over the occupied-tile list.

    data:      (T, bm, bn) dense zero-filled tiles
    tile_rows: (T,) int32 block-row id per tile
    tile_cols: (T,) int32 block-col id per tile
    x:         (N,) or (N, B) — padded internally to a ``bn`` multiple

    Each tile gathers its lane-aligned x slice whole, does one dense
    (bm, bn) @ (bn,) product, and scatter-adds into its block row — the
    order-free definition the scalar-prefetch walk kernel reproduces.
    """
    T, bm, bn = data.shape
    n = x.shape[0]
    Nb = max(-(-n // bn), 1)
    pad = [(0, Nb * bn - n)] + [(0, 0)] * (x.ndim - 1)
    xb = jnp.pad(x, pad).reshape((Nb, bn) + x.shape[1:])
    gathered = jnp.take(xb, tile_cols, axis=0)          # (T, bn[, B])
    contrib = jnp.einsum("tij,tj...->ti...", data, gathered,
                         precision=_EXACT)
    Mb = max(-(-num_rows // bm), 1)
    out = jnp.zeros((Mb, bm) + x.shape[1:], dtype=contrib.dtype)
    out = out.at[tile_rows].add(contrib)
    return out.reshape((Mb * bm,) + x.shape[1:])[:num_rows]


def tile_flat_spmv_ref(data: jnp.ndarray, xcols: jnp.ndarray,
                       trows: jnp.ndarray, x: jnp.ndarray,
                       num_rows: int) -> jnp.ndarray:
    """Flat-gather variant for the device path.

    ``xcols`` (T, bn) carries each tile's *remapped* per-lane x positions
    (the executor's augmented local+halo buffer has no block structure to
    index by block column), and padding tiles carry ``trows >= Mb`` so
    their scatter drops.  Unoccupied lanes point at position 0 and hold
    zero data, contributing exact zeros.
    """
    T, bm, bn = data.shape
    gathered = jnp.take(x, xcols, axis=0)               # (T, bn[, B])
    contrib = jnp.einsum("tij,tj...->ti...", data, gathered,
                         precision=_EXACT)
    Mb = max(-(-num_rows // bm), 1)
    out = jnp.zeros((Mb, bm) + x.shape[1:], dtype=contrib.dtype)
    out = out.at[trows].add(contrib, mode="drop")
    return out.reshape((Mb * bm,) + x.shape[1:])[:num_rows]


def bell_spmv_ref(blocks: jnp.ndarray, bcols: jnp.ndarray, x: jnp.ndarray) -> jnp.ndarray:
    """Block-ELL SpMV oracle.

    blocks: (Mb, K, bm, bn) dense blocks, zero-padded where inactive
    bcols:  (Mb, K) block-column index per slot (0 for padded slots)
    x:      (Nb * bn,)
    returns y: (Mb * bm,)
    """
    Mb, K, bm, bn = blocks.shape
    xb = x.reshape(-1, bn)                       # (Nb, bn)
    gathered = jnp.take(xb, bcols, axis=0)       # (Mb, K, bn)
    y = jnp.einsum("mkij,mkj->mi", blocks, gathered, precision=_EXACT)
    return y.reshape(Mb * bm)


def bell_spmm_ref(blocks: jnp.ndarray, bcols: jnp.ndarray, X: jnp.ndarray) -> jnp.ndarray:
    """Block-ELL SpMM oracle (sparse A @ dense X).

    X: (Nb * bn, B) -> returns (Mb * bm, B).
    """
    Mb, K, bm, bn = blocks.shape
    B = X.shape[1]
    Xb = X.reshape(-1, bn, B)                    # (Nb, bn, B)
    gathered = jnp.take(Xb, bcols, axis=0)       # (Mb, K, bn, B)
    Y = jnp.einsum("mkij,mkjb->mib", blocks, gathered,
                   precision=_EXACT)
    return Y.reshape(Mb * bm, B)
