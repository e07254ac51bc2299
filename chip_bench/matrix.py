"""The benchmark's own sparse matrices: a CSR container, and a tenant's
matrix built from its pattern generator and a seed.

The sparsity pattern is fixed by the tenant's entry in the configuration
(its ``pattern``, ``rows``, ``nnz``, ``pattern_params`` and
``structure_seed``), so the autotuner sees the same structure in every
run.  ``--seed`` draws only the stored values.  Nothing here imports the
program under test.
"""
from __future__ import annotations

import dataclasses
import importlib

import numpy as np

__all__ = ["Csr", "build_matrix"]


@dataclasses.dataclass(frozen=True)
class Csr:
    """CSR on the host: float64 values, int32 column ids, int64 row offsets."""

    shape: tuple
    values: np.ndarray
    col_index: np.ndarray
    row_ptr: np.ndarray

    @property
    def nrows(self) -> int:
        return int(self.shape[0])

    @property
    def ncols(self) -> int:
        return int(self.shape[1])

    @property
    def nnz(self) -> int:
        return int(self.col_index.shape[0])

    def row_ids(self) -> np.ndarray:
        """The row of every stored entry (int32)."""
        return np.repeat(np.arange(self.nrows, dtype=np.int32),
                         np.diff(self.row_ptr))


def _coo(config: dict):
    """The generator's entries, out-of-range ones dropped, mirrored where
    the pattern is symmetric."""
    gen = importlib.import_module(f"chip_bench.patterns.{config['pattern']}")
    M = int(config["rows"])
    rows, cols = gen.coo(M, int(config["nnz"]),
                         seed=int(config["structure_seed"]),
                         **config.get("pattern_params", {}))
    keep = (rows >= 0) & (rows < M) & (cols >= 0) & (cols < M)
    rows, cols = rows[keep], cols[keep]
    if gen.SYMMETRIC:
        rows, cols = np.concatenate([rows, cols]), np.concatenate([cols, rows])
    return rows, cols, (M, M)


def build_matrix(tenant: dict, seed: int, index: int = 0) -> Csr:
    """The tenant's pattern, entries sorted by (row, col) with duplicates
    merged, and float64 values drawn from ``seed``: independent standard
    normals, one per stored entry.  Tenant ``index`` > 0 draws from a
    stream of its own; tenant 0 from the one a one-tenant cell uses."""
    rows, cols, shape = _coo(tenant)
    key = np.unique(rows.astype(np.int64) * shape[1] + cols)
    rows, cols = np.divmod(key, shape[1])
    row_ptr = np.zeros(shape[0] + 1, dtype=np.int64)
    np.cumsum(np.bincount(rows, minlength=shape[0]), out=row_ptr[1:])
    rng = np.random.default_rng(np.random.SeedSequence(
        [seed, 1] + ([index] if index else [])))
    return Csr(shape=tuple(shape), values=rng.standard_normal(key.size),
               col_index=cols.astype(np.int32), row_ptr=row_ptr)
