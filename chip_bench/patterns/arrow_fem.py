"""cop20k_A-like arrowhead FEM pattern (the paper's §IV-D hot-spot).

A 1-D band mesh whose refined vertices (every ``1/hot_frac``-th one) carry
``dense_boost`` times the edges, renumbered so that the refined vertices
take the leading block of indices.  In matrix order about a quarter of all
x reads then hit the first eighth of the columns, while a BFS reordering
recovers the band.  The pattern of ``repro.data.matrices.arrow_fem``: the
same seed gives the same stored entries.
"""
from __future__ import annotations

import numpy as np

SYMMETRIC = True


def coo(M: int, nnz: int, *, seed: int, hot_frac: float = 0.125,
        dense_boost: float = 3.7):
    rng = np.random.default_rng(seed)
    stride = max(int(round(1.0 / hot_frac)), 2)
    refined = (np.arange(M) % stride) == 0
    k = max(int((nnz // 2) / (M * (1.0 + (dense_boost - 1.0) / stride))), 1)
    counts = np.where(refined, int(k * dense_boost), k).astype(np.int64)
    window = max(M // 64, 8)
    src = np.repeat(np.arange(M), counts)
    dst = src + rng.integers(1, window + 1, src.shape[0])
    ok = dst < M
    src, dst = src[ok], dst[ok]
    n_ref = int(refined.sum())
    perm = np.empty(M, dtype=np.int64)
    perm[refined] = np.arange(n_ref)
    perm[~refined] = n_ref + np.arange(M - n_ref)
    src, dst = perm[src], perm[dst]
    return (np.concatenate([src, np.arange(M)]),
            np.concatenate([dst, np.arange(M)]))
