"""Jit'd public wrappers around the Pallas kernels (+ jnp paths).

The pure-jnp path is the default execution path; ``use_kernel=True``
runs the Pallas kernel, compiled on the TPU and under ``interpret=True``
on the CPU, so every higher layer works identically on both.  For most
families the jnp path is the oracle of :mod:`repro.kernels.ref`; the seg
and split families serve a segmented chunk scan and one scatter update
per piece instead, and keep their slot-by-slot scatter-add as the oracle
only.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import ref
from .spmv_ell import ell_spmv as _ell_spmv_pallas
from .spmv_seg import seg_psum as _seg_psum_pallas
from .spmv_split import split_combine as _split_combine_pallas, \
    split_psum as _split_psum_pallas
from .spmv_tile import tile_contrib as _tile_contrib_pallas, \
    tile_walk_spmv as _tile_walk_pallas
from repro.core.partition import nnz_chunk_starts
from repro.core.sparse_matrix import EllMatrix, SegMatrix, SplitMatrix, \
    TileMatrix, csr_to_tile, hyb_cap_width

__all__ = ["SEG_CHUNK", "ell_spmv_ref", "ell_spmv", "hyb_spmv", "hyb_from_csr",
           "bell_spmv", "bell_spmm", "bell_from_bcsr", "seg_spmv",
           "seg_spmv_ref", "seg_from_csr", "piece_starts", "split_from_csr",
           "split_spmv", "split_spmv_ref", "split_flat_spmv", "tile_from_csr",
           "tile_spmv", "tile_spmv_ref", "tile_flat_spmv"]

#: Default elements per segmented chunk (lane-aligned).  Single source of
#: truth shared with the plan cost model's padding arithmetic.
SEG_CHUNK = 512

ell_spmv_ref = jax.jit(ref.ell_spmv_ref)
bell_spmv_ref = jax.jit(ref.bell_spmv_ref)
bell_spmm_ref = jax.jit(ref.bell_spmm_ref)
seg_spmv_ref = jax.jit(ref.seg_spmv_ref, static_argnames=("num_rows",))
split_spmv_ref = jax.jit(ref.split_spmv_ref, static_argnames=("num_rows",))
tile_spmv_ref = jax.jit(ref.tile_spmv_ref, static_argnames=("num_rows",))
tile_flat_spmv_ref = jax.jit(ref.tile_flat_spmv_ref,
                             static_argnames=("num_rows",))


def ell_spmv(data, cols, x, *, interpret: bool = False, **tiles):
    """Pallas ELL SpMV (TPU); set interpret=True on CPU.

    Accepts a multi-RHS block x of shape (N, B) as well as a single (N,)
    vector; the batched case vmaps the single-vector kernel over the
    trailing axis, so each column reproduces the per-vector result.
    """
    if jnp.asarray(x).ndim == 2:
        return jax.vmap(
            lambda xb: _ell_spmv_pallas(data, cols, xb, interpret=interpret,
                                        **tiles),
            in_axes=1, out_axes=1)(jnp.asarray(x))
    return _ell_spmv_pallas(data, cols, x, interpret=interpret, **tiles)


@functools.partial(jax.jit, static_argnames=("num_rows",))
def _overflow_add(y, rows, cols, vals, x, num_rows: int):
    xs = jnp.take(x, cols, axis=0)           # (O,) or (O, B)
    if xs.ndim == 2:
        vals = vals[:, None]
    return y.at[rows].add(vals * xs)


def hyb_from_csr(csr, *, lane: int | None = None,
                 sublane: int | None = None) -> EllMatrix:
    """Convert host CSRMatrix -> HYB (capped ELL + COO overflow tail).

    The ELL width is capped at :func:`~repro.core.sparse_matrix.hyb_cap_width`
    (lane-aligned p95 of row lengths), so skewed rows spill into the COO
    overflow arrays instead of inflating every row's padded width —
    the format :func:`hyb_spmv` executes.
    """
    from repro.core.sparse_matrix import ELL_LANE, ELL_SUBLANE, csr_row_nnz, \
        csr_to_ell
    lane = ELL_LANE if lane is None else lane
    sublane = ELL_SUBLANE if sublane is None else sublane
    cap = hyb_cap_width(csr_row_nnz(csr), lane=lane)
    return csr_to_ell(csr, lane=lane, sublane=sublane, max_width=cap)


def hyb_spmv(ell_data, ell_cols, ovf_rows, ovf_cols, ovf_vals, x,
             *, use_kernel: bool = False, interpret: bool = False):
    """HYB = padded-ELL kernel + COO overflow scatter-add tail.

    Accepts a single (N,) vector or a multi-RHS block (N, B), matching the
    other kernel wrappers; the overflow scatter broadcasts over the
    trailing batch axis."""
    if use_kernel:
        y = ell_spmv(ell_data, ell_cols, x, interpret=interpret)
    else:
        y = ell_spmv_ref(ell_data, ell_cols, x)
    if ovf_vals.shape[0]:
        y = _overflow_add(y, ovf_rows, ovf_cols, ovf_vals, x, num_rows=y.shape[0])
    return y


def _bell_walk_tables(blocks, bcols):
    """Flatten padded Block-ELL tables into a rectangular tile walk.

    Block-ELL *is* a dense tile walk whose walk table happens to be
    rectangular: slot (mb, k) streams tile ``mb*K + k`` against block
    column ``bcols[mb, k]``; padded slots hold zero blocks so the walk
    visits them harmlessly (counts = K everywhere).
    """
    Mb, K, bm, bn = blocks.shape
    data = jnp.asarray(blocks).reshape(Mb * K, bm, bn)
    counts = jnp.full((Mb,), K, dtype=jnp.int32)
    tid = jnp.arange(Mb * K, dtype=jnp.int32).reshape(Mb, K)
    return data, counts, tid, jnp.asarray(bcols, dtype=jnp.int32)


def bell_spmv(blocks, bcols, x, *, use_kernel: bool = False,
              interpret: bool = False):
    """Deprecated Block-ELL SpMV — absorbed by the tile family.

    Thin shim: the padded (Mb, K) Block-ELL tables are one special case
    of the bitmask-tiled walk (rectangular walk table, all slots
    visited), so the kernel path runs
    :func:`~repro.kernels.spmv_tile.tile_walk_spmv`.  New code should
    build a :class:`TileMatrix` via :func:`tile_from_csr` and call
    :func:`tile_spmv`.
    """
    from repro.core.spmv import _warn_deprecated
    _warn_deprecated("bell_spmv", "repro.kernels.ops.tile_spmv")
    if use_kernel:
        data, counts, tid, bc = _bell_walk_tables(blocks, bcols)
        return _tile_walk_pallas(data, counts, tid, bc, jnp.asarray(x),
                                 interpret=interpret)
    return bell_spmv_ref(blocks, bcols, x)


def bell_spmm(blocks, bcols, X, *, use_kernel: bool = False,
              interpret: bool = False, tile_b: int = 128):
    """Deprecated Block-ELL SpMM — absorbed by the tile family.

    Thin shim over the tile walk, vmapped over the RHS columns
    (``tile_b`` is accepted for signature compatibility and ignored).
    New code should call :func:`tile_spmv` with a (N, B) block.
    """
    from repro.core.spmv import _warn_deprecated
    _warn_deprecated("bell_spmm", "repro.kernels.ops.tile_spmv")
    del tile_b
    if use_kernel:
        data, counts, tid, bc = _bell_walk_tables(blocks, bcols)
        return jax.vmap(
            lambda xb: _tile_walk_pallas(data, counts, tid, bc, xb,
                                         interpret=interpret),
            in_axes=1, out_axes=1)(jnp.asarray(X))
    return bell_spmm_ref(blocks, bcols, X)


@functools.partial(jax.jit, static_argnames=("num_rows",))
def _seg_fixup(psum, piece_chunk, piece_lo, piece_hi, piece_row,
               num_rows: int):
    """Cross-chunk carry fix-up of the Pallas path: scatter per-(chunk,
    row) pieces into y.

    A piece covering in-chunk offsets [lo, hi] contributes
    ``psum[chunk, hi] - psum[chunk, lo-1]`` (0 when lo == 0) to its row:
    one scatter update per piece, not per stored slot.  Prefix
    differences stay chunk-local, so fp32 error is bounded by one
    chunk's scan, not the whole stream's — but it scales with the
    chunk's running sum, not with the row's own terms.
    """
    hi = psum[piece_chunk, piece_hi]
    lo = jnp.where(piece_lo > 0,
                   psum[piece_chunk, jnp.maximum(piece_lo - 1, 0)],
                   jnp.zeros((), dtype=psum.dtype))
    y = jnp.zeros((num_rows,), dtype=psum.dtype)
    return y.at[piece_row].add(hi - lo)


def piece_starts(num_chunks: int, chunk: int, piece_chunk,
                 piece_lo) -> np.ndarray:
    """(C, L) bool slab, True at each piece's first slot: where the
    served scan (:func:`_segmented_scan`) restarts its running sum."""
    starts = np.zeros((num_chunks, chunk), dtype=bool)
    starts[np.asarray(piece_chunk), np.asarray(piece_lo)] = True
    return starts


def _segmented_scan(v, starts):
    """Inclusive prefix sums along the lanes that restart at every
    ``starts`` lane, so each slot holds the sum of its own piece up to
    itself and nothing before the piece.  ``log2(L)`` steps; step ``s``
    adds the value ``s`` lanes to the left unless a start lies between
    (Hillis-Steele, segmented)."""
    s = 1
    while s < v.shape[-1]:
        pad = ((0, 0), (s, 0))
        v = jnp.where(starts, v, v + jnp.pad(v[:, :-s], pad))
        starts = starts | jnp.pad(starts[:, :-s], pad, constant_values=True)
        s *= 2
    return v


@functools.partial(jax.jit, static_argnames=("num_rows",))
def _seg_scan_spmv(vals, cols, starts, piece_chunk, piece_lo, piece_hi,
                   piece_row, x, num_rows: int):
    """The served seg pass: segmented scans of ``vals * x[cols]``, then
    one scatter update per piece, its scan at its last slot — no
    difference is taken, so a row's fp32 error is bounded by its own
    terms, not by the chunk's running sum before it.  Padded pieces
    (lo > hi) add an exact zero."""
    scan = _segmented_scan(vals * jnp.take(x, cols, axis=0), starts)
    part = jnp.where(piece_lo <= piece_hi, scan[piece_chunk, piece_hi],
                     jnp.zeros((), dtype=scan.dtype))
    return jnp.zeros((num_rows,), dtype=scan.dtype).at[piece_row].add(part)


def seg_spmv(seg: "SegMatrix | tuple", x, *, num_rows: int | None = None,
             use_kernel: bool = False, interpret: bool = False,
             tile_c: int = 8):
    """Nonzero-balanced segmented SpMV: y = A @ x over the chunked stream.

    ``seg`` is a host :class:`SegMatrix` (or the equivalent array tuple
    ``(vals, cols, starts, piece_chunk, piece_lo, piece_hi, piece_row)``,
    ``starts`` from :func:`piece_starts`).  Both paths scan the products
    within each chunk and sum rows with one scatter update per (chunk,
    row) piece, not per stored slot.  The default scans in jnp, restarting
    at each piece (:func:`_seg_scan_spmv`); ``use_kernel=True`` runs the
    Pallas per-chunk prefix-sum kernel (``interpret=True`` on CPU) and
    the prefix-difference fix-up (:func:`_seg_fixup`).  ``x`` may be (N,)
    or a multi-RHS block (N, B), one column at a time.
    :func:`seg_spmv_ref`, which scatter-adds every slot, is the oracle.
    """
    if isinstance(seg, SegMatrix):
        arrays = (seg.vals, seg.cols,
                  piece_starts(*seg.vals.shape, seg.piece_chunk,
                               seg.piece_lo),
                  seg.piece_chunk, seg.piece_lo, seg.piece_hi, seg.piece_row)
        if num_rows is None:
            num_rows = seg.shape[0]
    else:
        arrays = seg
        if num_rows is None:
            raise ValueError("num_rows is required with raw seg arrays")
    vals, cols, starts, p_chunk, p_lo, p_hi, p_row = map(jnp.asarray, arrays)

    def one(xb):
        if not use_kernel:
            return _seg_scan_spmv(vals, cols, starts, p_chunk, p_lo, p_hi,
                                  p_row, xb, num_rows=num_rows)
        psum = _seg_psum_pallas(vals, cols, xb, tile_c=tile_c,
                                interpret=interpret)
        return _seg_fixup(psum, p_chunk, p_lo, p_hi, p_row, num_rows)
    x = jnp.asarray(x)
    if x.ndim == 2:                     # multi-RHS: one column at a time
        return jax.vmap(one, in_axes=1, out_axes=1)(x)
    return one(x)


def seg_from_csr(csr, *, chunk: int = SEG_CHUNK, lane: int = 128,
                 sublane: int = 8) -> SegMatrix:
    """Convert host CSRMatrix -> nonzero-balanced SegMatrix.

    ``chunk`` is rounded up to a ``lane`` multiple and the chunk count to a
    ``sublane`` multiple (TPU tiling).  Chunk boundaries come from
    :func:`repro.core.partition.nnz_chunk_starts` — the same element-level
    work-distribution definition the partition layer owns — so the kernel
    grid and the Emu-side accounting agree on what a chunk is.
    """
    L = ((max(chunk, 1) + lane - 1) // lane) * lane
    nnz = csr.nnz
    starts = nnz_chunk_starts(nnz, L)
    C = starts.shape[0] - 1
    C_pad = ((C + sublane - 1) // sublane) * sublane

    vals = np.zeros((C_pad, L), dtype=np.float32)
    cols = np.zeros((C_pad, L), dtype=np.int32)
    rows = np.zeros((C_pad, L), dtype=np.int32)
    row_of_nnz = np.repeat(np.arange(csr.nrows, dtype=np.int64),
                           np.diff(csr.row_ptr))
    flat_c = np.arange(nnz, dtype=np.int64) // L
    flat_l = np.arange(nnz, dtype=np.int64) % L
    vals[flat_c, flat_l] = csr.values
    cols[flat_c, flat_l] = csr.col_index
    rows[flat_c, flat_l] = row_of_nnz

    # Pieces: maximal same-row runs within a chunk.  A new piece starts at
    # every chunk boundary and every row change; padded tail slots are
    # excluded entirely (they carry value 0 anyway).
    if nnz:
        is_start = np.zeros(nnz, dtype=bool)
        is_start[0] = True
        is_start[1:] = row_of_nnz[1:] != row_of_nnz[:-1]
        is_start[np.arange(0, nnz, L)] = True
        p_start = np.flatnonzero(is_start)
        p_end = np.concatenate([p_start[1:] - 1, [nnz - 1]])
        piece_chunk = (p_start // L).astype(np.int32)
        piece_lo = (p_start % L).astype(np.int32)
        piece_hi = (p_end % L).astype(np.int32)
        piece_row = row_of_nnz[p_start].astype(np.int32)
    else:
        piece_chunk = piece_lo = piece_hi = piece_row = np.zeros(0, np.int32)
    return SegMatrix(shape=csr.shape, chunk=L, vals=vals, cols=cols,
                     rows=rows, piece_chunk=piece_chunk, piece_lo=piece_lo,
                     piece_hi=piece_hi, piece_row=piece_row, nnz=nnz)


@functools.partial(jax.jit, static_argnames=("num_splits", "num_rows"))
def _split_fixup(psum, piece_split, piece_chunk, piece_lo, piece_hi,
                 piece_row, *, num_splits: int, num_rows: int):
    """Carry fix-up into per-split partials: (NS, Cs, L) -> (NS, R).

    Same prefix-difference contract as :func:`_seg_fixup`, but each piece
    lands in its *split's* partial row sum — stage 2 reduces the split
    axis afterwards, so no scatter ever crosses a split boundary.
    """
    hi = psum[piece_split, piece_chunk, piece_hi]
    lo = jnp.where(piece_lo > 0,
                   psum[piece_split, piece_chunk,
                        jnp.maximum(piece_lo - 1, 0)],
                   jnp.zeros((), dtype=psum.dtype))
    part = jnp.zeros((num_splits, num_rows), dtype=psum.dtype)
    return part.at[piece_split, piece_row].add(hi - lo)


@functools.partial(jax.jit, static_argnames=("num_splits", "num_rows"))
def _split_flat_fixup(psum, pieces, *, num_splits: int, num_rows: int):
    """Flat-slab variant for the device path: psum is (NS*Cs, L) and
    ``pieces`` is the (P, 5) table [flat_chunk, lo, hi, row, split]."""
    p_chunk, p_lo, p_hi, p_row, p_split = (pieces[:, 0], pieces[:, 1],
                                           pieces[:, 2], pieces[:, 3],
                                           pieces[:, 4])
    hi = psum[p_chunk, p_hi]
    lo = jnp.where(p_lo > 0, psum[p_chunk, jnp.maximum(p_lo - 1, 0)],
                   jnp.zeros((), dtype=psum.dtype))
    part = jnp.zeros((num_splits, num_rows), dtype=psum.dtype)
    return part.at[p_split, p_row].add(hi - lo)


def split_spmv(spl: "SplitMatrix | tuple", x, *, num_rows: int | None = None,
               use_kernel: bool = False, interpret: bool = False,
               tile_c: int = 8):
    """Split-nnz two-stage SpMV: y = A @ x with split-K partials.

    ``spl`` is a host :class:`SplitMatrix` (or the equivalent array tuple
    ``(vals, cols, rows, piece_split, piece_chunk, piece_lo, piece_hi,
    piece_row)``).  The jnp scatter-add oracle is the default execution
    path; ``use_kernel=True`` runs stage 1 (Pallas per-chunk prefix sums
    on a 2-D (split, chunk-tile) grid), the jit'd per-split carry fix-up,
    and stage 2 (Pallas split-axis combine).
    """
    if isinstance(spl, SplitMatrix):
        arrays = (spl.vals, spl.cols, spl.rows, spl.piece_split,
                  spl.piece_chunk, spl.piece_lo, spl.piece_hi, spl.piece_row)
        if num_rows is None:
            num_rows = spl.shape[0]
    else:
        arrays = spl
        if num_rows is None:
            raise ValueError("num_rows is required with raw split arrays")
    vals, cols, rows, p_s, p_c, p_lo, p_hi, p_row = map(jnp.asarray, arrays)
    NS = int(vals.shape[0])
    if use_kernel:
        def one(xb):
            psum = _split_psum_pallas(vals, cols, xb, tile_c=tile_c,
                                      interpret=interpret)
            part = _split_fixup(psum, p_s, p_c, p_lo, p_hi, p_row,
                                num_splits=NS, num_rows=num_rows)
            return _split_combine_pallas(part, interpret=interpret)
        if jnp.asarray(x).ndim == 2:    # multi-RHS: vmap the kernel path
            return jax.vmap(one, in_axes=1, out_axes=1)(jnp.asarray(x))
        return one(x)
    return split_spmv_ref(vals, cols, rows, x, num_rows=num_rows)


def split_flat_spmv(vals, cols, starts, pieces, x, *, num_rows: int,
                    num_splits: int, use_kernel: bool = False,
                    interpret: bool = False, tile_c: int = 8):
    """Split SpMV over the *flattened* (NS*Cs, L) device slab.

    The distributed executor stacks every shard's slab into one uniform
    (C, L) operand, so the split structure travels in the (P, 5) int32
    piece table [flat_chunk, lo, hi, row, split] instead of a third slab
    axis (padded piece rows hold [0, 1, 0, 0, 0] — an exact zero), with
    ``starts`` its :func:`piece_starts` slab.  The default path is the
    seg pass on the flat slab (:func:`seg_spmv`, the table's first four
    columns): the split axis only partitions the stream.  The kernel
    path is the two-stage pipeline sharing
    :func:`~repro.kernels.spmv_seg.seg_psum` for stage 1.
    """
    if not use_kernel:
        return seg_spmv((vals, cols, starts)
                        + tuple(pieces[:, k] for k in range(4)),
                        x, num_rows=num_rows)

    def one(xb):
        psum = _seg_psum_pallas(vals, cols, xb, tile_c=tile_c,
                                interpret=interpret)
        part = _split_flat_fixup(psum, pieces, num_splits=num_splits,
                                 num_rows=num_rows)
        return _split_combine_pallas(part, interpret=interpret)
    if jnp.asarray(x).ndim == 2:
        return jax.vmap(one, in_axes=1, out_axes=1)(jnp.asarray(x))
    return one(x)


def split_from_csr(csr, num_splits: int, *, chunk: int = SEG_CHUNK,
                   lane: int = 128, sublane: int = 8) -> SplitMatrix:
    """Convert host CSRMatrix -> split-nnz SplitMatrix.

    The seg chunk grid is cut into ``num_splits`` contiguous groups of
    ``Cs = ceil(C / num_splits)`` chunks; ``num_splits`` is clamped to
    [1, C] so the slab never holds an all-padding split.  Unlike
    :func:`seg_from_csr` the per-split chunk count is *not* sublane-padded
    — stage 1 adapts its tile to a divisor of Cs — so a small split count
    never multiplies the padding by NS.
    """
    L = ((max(chunk, 1) + lane - 1) // lane) * lane
    nnz = csr.nnz
    starts = nnz_chunk_starts(nnz, L)
    C = starts.shape[0] - 1
    ns = max(1, min(int(num_splits), C))
    Cs = (C + ns - 1) // ns

    vals = np.zeros((ns, Cs, L), dtype=np.float32)
    cols = np.zeros((ns, Cs, L), dtype=np.int32)
    rows = np.zeros((ns, Cs, L), dtype=np.int32)
    row_of_nnz = np.repeat(np.arange(csr.nrows, dtype=np.int64),
                           np.diff(csr.row_ptr))
    flat_g = np.arange(nnz, dtype=np.int64) // L
    s_idx = flat_g // Cs
    c_idx = flat_g % Cs
    l_idx = np.arange(nnz, dtype=np.int64) % L
    vals[s_idx, c_idx, l_idx] = csr.values
    cols[s_idx, c_idx, l_idx] = csr.col_index
    rows[s_idx, c_idx, l_idx] = row_of_nnz

    # Pieces: identical runs to seg_from_csr (cut at row changes and chunk
    # boundaries); the owning chunk is just re-indexed as (split, within).
    if nnz:
        is_start = np.zeros(nnz, dtype=bool)
        is_start[0] = True
        is_start[1:] = row_of_nnz[1:] != row_of_nnz[:-1]
        is_start[np.arange(0, nnz, L)] = True
        p_start = np.flatnonzero(is_start)
        p_end = np.concatenate([p_start[1:] - 1, [nnz - 1]])
        p_g = p_start // L
        piece_split = (p_g // Cs).astype(np.int32)
        piece_chunk = (p_g % Cs).astype(np.int32)
        piece_lo = (p_start % L).astype(np.int32)
        piece_hi = (p_end % L).astype(np.int32)
        piece_row = row_of_nnz[p_start].astype(np.int32)
    else:
        piece_split = piece_chunk = piece_lo = piece_hi = piece_row = \
            np.zeros(0, np.int32)
    return SplitMatrix(shape=csr.shape, chunk=L, num_splits=ns, vals=vals,
                       cols=cols, rows=rows, piece_split=piece_split,
                       piece_chunk=piece_chunk, piece_lo=piece_lo,
                       piece_hi=piece_hi, piece_row=piece_row, nnz=nnz)


def tile_from_csr(csr, *, bm: int | None = None,
                  bn: int | None = None) -> TileMatrix:
    """Convert host CSRMatrix -> bitmask-tiled :class:`TileMatrix`.

    Thin wrapper over :func:`repro.core.sparse_matrix.csr_to_tile`; tiles
    default to the fp32 native (8, 128) vector tile.  The format
    :func:`tile_spmv` executes — and the fifth per-shard kernel family
    the plan grid / lowering / autotuner select as ``"tile"``.
    """
    from repro.core.sparse_matrix import ELL_LANE, ELL_SUBLANE
    return csr_to_tile(csr, bm=ELL_SUBLANE if bm is None else bm,
                       bn=ELL_LANE if bn is None else bn)


def _tile_walk_tables(tile: TileMatrix):
    """Flatten the pointer grid into (counts, tid, bc) prefetch tables.

    K = max occupied tiles per block row; slots past ``counts[mb]`` clamp
    to a valid tile id (their contribution is masked in-kernel), so the
    index maps never read out of bounds.
    """
    counts = np.diff(tile.tile_ptr).astype(np.int32)        # (Mb,)
    Mb = counts.shape[0]
    T = tile.num_tiles
    K = max(int(counts.max()) if counts.size else 0, 1)
    tid = tile.tile_ptr[:-1, None].astype(np.int64) + np.arange(K)[None, :]
    tid = np.minimum(tid, max(T - 1, 0)).astype(np.int32)
    bc = (tile.tile_cols[tid.reshape(-1)].reshape(Mb, K)
          if T else np.zeros((Mb, K), np.int32))
    return counts, tid, bc


def tile_spmv(tile: TileMatrix, x, *, num_rows: int | None = None,
              use_kernel: bool = False, interpret: bool = False):
    """Bitmask-tiled SpMV: y = A @ x over the occupied-tile walk.

    Same contract as the other ops: the jnp gather/einsum/scatter oracle
    (:func:`repro.kernels.ref.tile_spmv_ref`) is the default execution
    path; ``use_kernel=True`` runs the Pallas scalar-prefetch tile walk
    (``interpret=True`` on CPU).  ``x`` may be a single (N,) vector or a
    multi-RHS block (N, B); the kernel path vmaps over the trailing axis.
    """
    if num_rows is None:
        num_rows = tile.shape[0]
    if not use_kernel or tile.num_tiles == 0:
        return tile_spmv_ref(jnp.asarray(tile.data),
                             jnp.asarray(tile.tile_rows),
                             jnp.asarray(tile.tile_cols),
                             jnp.asarray(x), num_rows=num_rows)
    counts, tid, bc = _tile_walk_tables(tile)
    bn = tile.bn
    xa = jnp.asarray(x)
    n = xa.shape[0]
    Nb = max(-(-n // bn), 1)
    pad = [(0, Nb * bn - n)] + [(0, 0)] * (xa.ndim - 1)
    xp = jnp.pad(xa, pad)

    def one(xb):
        y = _tile_walk_pallas(jnp.asarray(tile.data), jnp.asarray(counts),
                              jnp.asarray(tid), jnp.asarray(bc), xb,
                              interpret=interpret)
        return y[:num_rows]
    if xa.ndim == 2:
        return jax.vmap(one, in_axes=1, out_axes=1)(xp)
    return one(xp)


def tile_flat_spmv(data, xcols, trows, x, *, num_rows: int,
                   use_kernel: bool = False, interpret: bool = False):
    """Tile SpMV over the *flat pre-gathered* device operands.

    The distributed executor has no block grid to index — x lives in the
    remapped augmented [local ++ halo] buffer — so each tile carries its
    per-lane x positions ``xcols`` (T, bn) and block row ``trows`` (T,)
    (padding tiles point past the last block row and drop).  The oracle
    path is :func:`repro.kernels.ref.tile_flat_spmv_ref`; the kernel path
    gathers x lanes with jnp (like the HYB overflow scatter) and runs the
    dense per-tile FMA stream through the Pallas ``tile_contrib`` kernel.
    """
    T, bm, bn = data.shape
    if not use_kernel:
        return tile_flat_spmv_ref(data, xcols, trows, x, num_rows=num_rows)
    Mb = max(-(-num_rows // bm), 1)

    def one(xb):
        xg = jnp.take(xb, xcols, axis=0)                 # (T, bn)
        contrib = _tile_contrib_pallas(data, xg, interpret=interpret)
        out = jnp.zeros((Mb, bm), dtype=contrib.dtype)
        out = out.at[trows].add(contrib, mode="drop")
        return out.reshape(Mb * bm)[:num_rows]
    if jnp.asarray(x).ndim == 2:
        return jax.vmap(one, in_axes=1, out_axes=1)(jnp.asarray(x))
    return one(jnp.asarray(x))


def bell_from_bcsr(bcsr) -> tuple[np.ndarray, np.ndarray]:
    """Deprecated: convert host BcsrMatrix -> padded Block-ELL arrays.

    K = max blocks per block-row; padded slots hold zero blocks and bcol 0,
    which the kernels treat as a no-op contribution.  Block-ELL is now a
    special case of the bitmask-tiled family — build a
    :class:`TileMatrix` with :func:`tile_from_csr` instead (pointer-grid
    walk, no padded slots, occupancy bitmask).
    """
    from repro.core.spmv import _warn_deprecated
    _warn_deprecated("bell_from_bcsr", "repro.kernels.ops.tile_from_csr")
    Mb = bcsr.block_row_ptr.shape[0] - 1
    bm, bn = bcsr.block_shape
    per_row = np.diff(bcsr.block_row_ptr)
    K = max(int(per_row.max()) if Mb else 1, 1)
    blocks = np.zeros((Mb, K, bm, bn), dtype=bcsr.blocks.dtype)
    bcols = np.zeros((Mb, K), dtype=np.int32)
    for r in range(Mb):
        lo, hi = int(bcsr.block_row_ptr[r]), int(bcsr.block_row_ptr[r + 1])
        blocks[r, : hi - lo] = bcsr.blocks[lo:hi]
        bcols[r, : hi - lo] = bcsr.block_cols[lo:hi]
    return blocks, bcols
