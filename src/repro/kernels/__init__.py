"""Pallas SpMV kernels + pure-jnp oracles (``ref.py``) + jit'd wrappers
(``ops.py``).

Five kernel families, one per sparse format/work-distribution choice:

* **ELL** (``spmv_ell.py``) — row-tiled padded-ELL SpMV (+ COO overflow
  tail = HYB via :func:`hyb_spmv`).  Grid is shape-aware: (rows, width)
  tiles, so one power-law row widens every tile's reduction.
* **Segmented** (``spmv_seg.py``) — nonzero-balanced merge-path-style
  SpMV: the nnz stream is cut into equal-size chunks, the kernel emits
  within-chunk prefix sums, and a jit'd cross-chunk carry fix-up
  assembles rows.  Grid is load-balance-aware: every step owns the same
  number of non-zeros regardless of row skew (the TPU analogue of the
  paper's nonzero work distribution, §III-C).
* **Split** (``spmv_split.py``) — split-nnz *two-stage* SpMV (split-K):
  the seg chunk grid is further cut into NS splits, stage 1 fills a 2-D
  (split, chunk) grid of partial accumulators, stage 2 is a tiny
  split-axis combine.  Cures the paper's §IV-D monster-row hot-spot at
  *shard* granularity — a one-row shard still fills the whole grid.
* **Tile** (``spmv_tile.py``) — bitmask-tiled SpMV: a coarse pointer
  grid over dense (8, 128) tiles plus per-tile occupancy bitmasks.  The
  scalar-prefetch walk streams whole tiles with dense FMAs and **no
  per-element column indices**, skipping empty tiles via the pointer
  level — the blocked format for banded / block-structured matrices,
  where ELL pads and seg wastes scan work.  The old MXU Block-ELL
  (``bell_*``) is absorbed as a special case of this walk; its ops
  survive as warn-once deprecated shims.

Every kernel has the same contract: a pure-jnp default execution path
(the oracle, except for seg and split, which serve a segmented chunk scan
with one scatter update per piece and keep the slot-by-slot scatter-add
as their oracle), ``use_kernel=True`` for the Pallas path (compiled on the
TPU), and ``interpret=True`` to run the Pallas path on CPU; the device
executor derives ``interpret`` from its mesh's platform.  The public API is
re-exported here (from ``ops.py``), so callers write
``from repro.kernels import ell_spmv`` without caring which file owns the
kernel.

Examples
--------
The ELL oracle against a dense product:

>>> import numpy as np
>>> from repro.kernels import ell_spmv_ref
>>> data = np.array([[2.0, 0.0], [1.0, 3.0]], np.float32)
>>> cols = np.array([[1, 0], [0, 1]], np.int32)
>>> x = np.array([1.0, 10.0], np.float32)
>>> np.asarray(ell_spmv_ref(data, cols, x)).tolist()   # [2*10, 1*1+3*10]
[20.0, 31.0]

The segmented path built straight from a CSR matrix:

>>> from repro.core.sparse_matrix import csr_from_coo, csr_to_dense
>>> from repro.kernels import seg_from_csr, seg_spmv
>>> A = csr_from_coo(np.array([0, 1, 1]), np.array([1, 0, 1]),
...                  np.array([5.0, 2.0, 4.0]), (2, 2))
>>> seg = seg_from_csr(A, chunk=128)
>>> y = np.asarray(seg_spmv(seg, np.array([1.0, 2.0], np.float32)))
>>> np.allclose(y, csr_to_dense(A) @ np.array([1.0, 2.0]))
True

The split-K path from the same matrix (two splits over the chunk grid):

>>> from repro.kernels import split_from_csr, split_spmv
>>> spl = split_from_csr(A, 2, chunk=128)
>>> y2 = np.asarray(split_spmv(spl, np.array([1.0, 2.0], np.float32)))
>>> np.allclose(y2, y)
True

The bitmask-tiled path from the same matrix (one occupied (8, 128) tile):

>>> from repro.kernels import tile_from_csr, tile_spmv
>>> tl = tile_from_csr(A)
>>> tl.num_tiles
1
>>> y3 = np.asarray(tile_spmv(tl, np.array([1.0, 2.0], np.float32)))
>>> np.allclose(y3, y)
True
"""
from .ops import (bell_from_bcsr, bell_spmm, bell_spmv, ell_spmv,
                  ell_spmv_ref, hyb_spmv, seg_from_csr, seg_spmv,
                  seg_spmv_ref, split_flat_spmv, split_from_csr, split_spmv,
                  split_spmv_ref, tile_flat_spmv, tile_from_csr, tile_spmv,
                  tile_spmv_ref)

__all__ = ["ell_spmv", "ell_spmv_ref", "hyb_spmv", "bell_spmv", "bell_spmm",
           "bell_from_bcsr", "seg_spmv", "seg_spmv_ref", "seg_from_csr",
           "split_spmv", "split_spmv_ref", "split_from_csr",
           "split_flat_spmv", "tile_spmv", "tile_spmv_ref", "tile_from_csr",
           "tile_flat_spmv"]
