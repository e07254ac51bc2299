"""Banded FEM pattern (audikw_1-like when ``bandwidth`` is M / 100).

Entries fall within ``bandwidth`` of the diagonal, except a
``scatter_frac`` share placed anywhere in the row (real FEM matrices are
never perfectly banded), plus the whole diagonal; the pattern is mirrored
to be symmetric.  The pattern of ``repro.data.matrices.banded``: the same
seed gives the same stored entries.
"""
from __future__ import annotations

import numpy as np

SYMMETRIC = True


def coo(M: int, nnz: int, *, seed: int, bandwidth: int,
        scatter_frac: float = 0.12):
    rng = np.random.default_rng(seed)
    n = nnz // 2 + M
    rows = rng.integers(0, M, n)
    cols = rows + rng.integers(-bandwidth, bandwidth + 1, n)
    n_sc = int(n * scatter_frac)
    if n_sc:
        cols[:n_sc] = rng.integers(0, M, n_sc)
    return (np.concatenate([rows, np.arange(M)]),
            np.concatenate([cols, np.arange(M)]))
