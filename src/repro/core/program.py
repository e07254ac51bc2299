"""The lowered SpMV program IR: per-shard heterogeneous kernels, one executor.

The paper's hot-spot result (§IV-D) is fundamentally *local*: sparsity
structure differs shard-to-shard, so one global (layout, kernel) choice
under-serves skewed shards while over-paying on regular ones — the
per-region strategy selection of feature-based SpMV optimization (Elafrou
et al., 2017), resolved per-nodelet as the Emu programming studies
recommend (Hein et al.).  This module is the single lowering path that
makes that selectable:

* :func:`lower` — ``lower(csr, plan)`` turns a host CSR matrix plus an
  :class:`~repro.core.spmv.SpmvPlan` into an :class:`SpmvProgram`: the
  reordered matrix, partition, vector layouts, exact traffic accounting,
  and one :class:`ShardStage` per shard.  Each stage independently holds
  an ``ell`` slab, a ``seg`` chunk stream, a ``hyb`` capped-ELL + COO
  overflow pair, a ``split`` two-stage split-nnz slab, or a ``tile``
  bitmask-tiled pointer grid (``plan.shard_kernels``); the exchange
  prologue
  (all-gather vs halo all-to-all) is part of the program, not of any
  particular executor.
* :func:`relower` — rebuilds **only** the stages whose kernel changed
  (same base: layout/distribution/reordering), sharing every other stage
  with the old program; exchange-policy changes (uniform or per-shard)
  share *all* stages.  This is the per-shard double-buffered swap the
  serving rebalancer uses for hot-shard-only re-plans
  (``serve/rebalance.py``).
* :func:`execute` — one entry point, three backends:

  - ``"numpy"``: the exact host oracle (float64, bitwise-stable batched
    multi-RHS) — the correctness reference the device answers are
    checked against;
  - ``"shard_map"``: the device executor, which ``SparseMatrixEngine``
    serves through on a mesh.  One ``shard_map`` program runs every
    shard and carries only the kernel families its stages run: a
    one-family program calls that family's pass, and a heterogeneous one
    dispatches per shard with a ``lax.switch`` over its families, so it
    still lowers to a single SPMD computation;
  - ``"emu"``: the Emu timeline probe (:func:`probe_program`) — the
    migratory-thread cost of the same (matrix, partition, layout) walk,
    which is what the autotuner's simulator re-ranking runs.

Every backend consumes the same :class:`SpmvProgram`, so the numpy
oracle, the TPU program, and the Emu model cannot drift apart.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Callable

import numpy as np

from .emu import EmuConfig, EmuResult, run_spmv
from .layout import VectorLayout, make_layout
from .migration import TrafficReport, count_migrations, remote_access_matrix
from .partition import Partition, make_partition
from .reorder import reordering_permutation
from .spans import span
from .plan import split_meta
from .sparse_matrix import CSRMatrix, ELL_LANE, ELL_SUBLANE, EllMatrix, \
    SegMatrix, SplitMatrix, TileMatrix, csr_to_ell
from .spmv import PLAN_KERNELS, SpmvPlan
from repro.kernels import ops as kops
from repro.kernels.spmv_tile import TILES_PER_STEP
from repro.kernels.tiling import round_up

__all__ = ["ShardStage", "SpmvProgram", "lower", "relower", "execute",
           "make_program_spmv_fn", "build_program_step", "kernel_interpret",
           "probe_program", "scatter_x", "gather_b"]


@dataclasses.dataclass(frozen=True)
class ShardStage:
    """One shard's stage of a lowered program: its kernel + device payload.

    ``kernel`` selects the format actually stored: ``"ell"`` (uncapped
    padded slab) and ``"hyb"`` (p95-capped slab + COO overflow, see
    :func:`~repro.kernels.ops.hyb_from_csr`) populate ``ell``; ``"seg"``
    populates ``seg``; ``"split"`` populates ``split`` (the split-nnz
    two-stage slab, NS partial accumulators + combine); ``"tile"``
    populates ``tile`` (the bitmask-tiled pointer grid over dense
    (8, 128) tiles).  ``rows``/``row_offset`` locate the shard's row
    range in the program's (reordered) matrix.
    """

    shard: int
    kernel: str                    # "ell" | "seg" | "hyb" | "split" | "tile"
    rows: int                      # true row count
    row_offset: int                # absolute first row
    nnz: int
    ell: EllMatrix | None = None   # kernel in ("ell", "hyb")
    seg: SegMatrix | None = None   # kernel == "seg"
    split: SplitMatrix | None = None   # kernel == "split"
    tile: TileMatrix | None = None     # kernel == "tile"

    @property
    def payload(self):
        """The format this stage's kernel family stores."""
        return getattr(self, _FAMILIES[self.kernel].field)


# --------------------------------------------------------------------------
# kernel families: each one's device format, built, stacked, run and
# counted in one place (the ``_FAMILIES`` table below)
# --------------------------------------------------------------------------

def _resolved_split_count(csr: CSRMatrix, requested: int) -> int:
    """The split count a shard's CSR lowers with: the request (or the
    :func:`~repro.core.plan.split_meta` policy when the request is
    0/absent), clamped to the shard's chunk count exactly as
    :func:`~repro.kernels.ops.split_from_csr` clamps it — so
    :func:`relower` can compare effective counts, not raw requests."""
    L = round_up(kops.SEG_CHUNK, ELL_LANE)
    C = max(-(-csr.nnz // L), 1)
    ns = requested if requested > 0 else \
        split_meta(csr.nnz, int(np.diff(csr.row_ptr).max(initial=0)))
    return max(1, min(int(ns), C))


def _ell_payload(csr: CSRMatrix, num_splits: int) -> EllMatrix:
    ell = csr_to_ell(csr)
    if ell.overflow_vals.size:
        raise AssertionError("uncapped ELL conversion cannot overflow")
    return ell


def _stack_ell(payloads, R: int, remap) -> dict:
    """The ELL slab of the ell and hyb stages, (S, R, W) at the widest
    shard's width, and the hyb COO overflow, (S, O) at the longest tail
    (at least 1).  Other shards, and shards without a tail, hold zeros:
    value 0 at position 0 adds an exact zero."""
    ells = [e for e in payloads if e is not None]
    S, W = len(payloads), max(e.width for e in ells)
    O = max(max(e.overflow_vals.size for e in ells), 1)
    ell_data = np.zeros((S, R, W), dtype=np.float32)
    ell_cols = np.zeros((S, R, W), dtype=np.int32)
    ovf_rows = np.zeros((S, O), dtype=np.int32)
    ovf_cols = np.zeros((S, O), dtype=np.int32)
    ovf_vals = np.zeros((S, O), dtype=np.float32)
    for p, e in enumerate(payloads):
        if e is None:
            continue
        r, w = e.data.shape
        ell_data[p, :r, :w] = e.data
        ell_cols[p, :r, :w] = remap(e.cols, e.data, p)
        n = e.overflow_vals.size
        ovf_rows[p, :n] = e.overflow_rows
        ovf_cols[p, :n] = remap(e.overflow_cols, e.overflow_vals, p)
        ovf_vals[p, :n] = e.overflow_vals
    return dict(ell_data=ell_data, ell_cols=ell_cols, ovf_rows=ovf_rows,
                ovf_cols=ovf_cols, ovf_vals=ovf_vals)


def _stack_chunks(payloads, R: int, remap) -> dict:
    """The chunk slab of the seg and split stages, (S, C, L).

    A split slab (NS, Cs, L) flattens into it (a seg slab is one split):
    the split structure travels in the piece table, widened to 5 columns
    [flat_chunk, lo, hi, row, split] (padded rows [0, 1, 0, 0, 0] are an
    exact zero).  ``seg_starts`` marks each piece's first slot, where the
    jnp pass's scan restarts.  ``NS``, the widest split count, is the
    split pass's static argument.
    """
    slabs = [s for s in payloads if s is not None]
    L = slabs[0].chunk
    if any(s.chunk != L for s in slabs):
        raise AssertionError("seg/split stages must share one chunk size")
    # round the chunk count up to the sublane so the Pallas scan's tiling
    # always divides it
    S = len(payloads)
    C = round_up(max(s.vals.size // L for s in slabs), ELL_SUBLANE)
    Pp = max(max(s.n_pieces for s in slabs), 1)
    seg_vals = np.zeros((S, C, L), dtype=np.float32)
    seg_cols = np.zeros((S, C, L), dtype=np.int32)
    seg_starts = np.zeros((S, C, L), dtype=bool)
    seg_pieces = np.zeros((S, Pp, 5), dtype=np.int32)
    seg_pieces[:, :, 1] = 1           # (lo=1, hi=0, row=0, split=0) -> zero
    for p, s in enumerate(payloads):
        if s is None:
            continue
        ns = getattr(s, "num_splits", 1)
        split = getattr(s, "piece_split", 0)
        fv = s.vals.reshape(-1, L)
        nc, n = fv.shape[0], s.n_pieces
        seg_vals[p, :nc] = fv
        seg_cols[p, :nc] = remap(s.cols.reshape(-1, L), fv, p)
        pc = seg_pieces[p, :n]
        pc[:, 0] = split * (nc // ns) + s.piece_chunk
        pc[:, 1] = s.piece_lo
        pc[:, 2] = s.piece_hi
        pc[:, 3] = s.piece_row
        pc[:, 4] = split
        seg_starts[p, :nc] = kops.piece_starts(nc, L, pc[:, 0], s.piece_lo)
    return dict(seg_vals=seg_vals, seg_cols=seg_cols, seg_starts=seg_starts,
                seg_pieces=seg_pieces,
                NS=max(getattr(s, "num_splits", 1) for s in slabs))


def _stack_tiles(payloads, R: int, remap) -> dict:
    """The tile stream of the tile stages, (S, T, bm, bn).

    Each tile's block-column id expands into per-lane x positions
    (``tile_xcol``) — the augmented exchange buffer has no block grid to
    index, so the remap runs on the expanded lanes, with *nonzero lane
    occupancy* as the remap values (dead / stored-zero-only lanes keep
    position 0 and contribute exact zeros); padding tiles point their
    block row (``tile_brow``) one past the last block so the scatter
    drops them.
    """
    tiles = [t for t in payloads if t is not None]
    bm, bn = tiles[0].bm, tiles[0].bn
    if any((t.bm, t.bn) != (bm, bn) for t in tiles):
        raise AssertionError("tile stages must share one tile shape")
    # the tile count rounds up to the tile kernel's tiles per grid step
    S = len(payloads)
    Tp = round_up(max(max(t.num_tiles for t in tiles), 1), TILES_PER_STEP)
    tile_data = np.zeros((S, Tp, bm, bn), dtype=np.float32)
    tile_xcol = np.zeros((S, Tp, bn), dtype=np.int32)
    tile_brow = np.full((S, Tp), -(-R // bm), dtype=np.int32)
    for p, t in enumerate(payloads):
        if t is None or not t.num_tiles:
            continue
        T = t.num_tiles
        tile_data[p, :T] = t.data
        gcols = np.minimum(
            t.tile_cols[:, None].astype(np.int64) * bn
            + np.arange(bn, dtype=np.int64)[None, :],
            t.shape[1] - 1)                            # (T, bn) global ids
        lane_nz = (t.data != 0).any(axis=1).astype(np.float32)
        tile_xcol[p, :T] = remap(np.where(lane_nz != 0, gcols, 0), lane_nz, p)
        tile_brow[p, :T] = t.tile_rows
    return dict(tile_data=tile_data, tile_xcol=tile_xcol, tile_brow=tile_brow)


def _ell_pass(a, x, num_rows, num_splits, use_kernel, interpret):
    if use_kernel:
        return kops.ell_spmv(a["ell_data"], a["ell_cols"], x,
                             interpret=interpret)
    return kops.ell_spmv_ref(a["ell_data"], a["ell_cols"], x)


def _hyb_pass(a, x, num_rows, num_splits, **kw):
    return kops.hyb_spmv(a["ell_data"], a["ell_cols"], a["ovf_rows"],
                         a["ovf_cols"], a["ovf_vals"], x, **kw)


def _seg_pass(a, x, num_rows, num_splits, **kw):
    pc = a["seg_pieces"]
    return kops.seg_spmv((a["seg_vals"], a["seg_cols"], a["seg_starts"],
                          pc[:, 0], pc[:, 1], pc[:, 2], pc[:, 3]), x,
                         num_rows=num_rows, **kw)


def _split_pass(a, x, num_rows, num_splits, **kw):
    return kops.split_flat_spmv(a["seg_vals"], a["seg_cols"],
                                a["seg_starts"], a["seg_pieces"], x,
                                num_rows=num_rows, num_splits=num_splits, **kw)


def _tile_pass(a, x, num_rows, num_splits, **kw):
    return kops.tile_flat_spmv(a["tile_data"], a["tile_xcol"],
                               a["tile_brow"], x, num_rows=num_rows, **kw)


@dataclasses.dataclass(frozen=True)
class _Family:
    """One kernel family's device format: the :class:`ShardStage` payload
    ``field`` it stores; ``build(csr, num_splits)``, that payload from a
    shard's CSR; ``keys``, one operand set's arrays; ``stack(payloads, R,
    remap)``, the payloads over the shards (``None`` for other families'
    shards); ``run(a, x, num_rows, num_splits, use_kernel=, interpret=)``,
    its device pass over one shard's set ``a`` and one vector; and
    ``scatter_updates(payload)``, the scatter-add updates that pass runs a
    vector (the padding that evens the shards adds exact zeros, uncounted);
    and ``block``, whether its jnp pass takes a multi-RHS (n, B) buffer
    whole, with one gather of x a request, where the others run in a
    device loop a vector at a time.
    """
    field: str
    keys: tuple
    build: Callable
    stack: Callable
    run: Callable
    scatter_updates: Callable
    block: bool = False


_ELL_KEYS = ("ell_data", "ell_cols")
_CHUNK_KEYS = ("seg_vals", "seg_cols", "seg_starts", "seg_pieces")

#: One entry per family of ``PLAN_KERNELS``.  ell and hyb share the ELL
#: slab, seg and split the chunk slab.  Scatter updates: one per piece for
#: seg and split (the carry fix-up), one per overflow entry for hyb, none
#: for ell (rows sum along the lanes) and tile (whole tile rows).  Only the
#: chunk families take a block whole: a batched gather lays the B values
#: of each index on 128 padded lanes, which the chunk pass bounds by
#: walking its slab in groups (``kops.SEG_GROUP_BYTES``), and which on an
#: ELL slab of audikw_1's size would need about 62 GB.
_FAMILIES = {
    "ell": _Family("ell", _ELL_KEYS, _ell_payload, _stack_ell, _ell_pass,
                   lambda e: 0),
    "seg": _Family("seg", _CHUNK_KEYS,
                   lambda csr, ns: kops.seg_from_csr(csr), _stack_chunks,
                   _seg_pass, lambda s: s.n_pieces, block=True),
    "hyb": _Family("ell", _ELL_KEYS + ("ovf_rows", "ovf_cols", "ovf_vals"),
                   lambda csr, ns: kops.hyb_from_csr(csr), _stack_ell,
                   _hyb_pass, lambda e: int(e.overflow_vals.size)),
    "split": _Family("split", _CHUNK_KEYS,
                     lambda csr, ns: kops.split_from_csr(
                         csr, _resolved_split_count(csr, ns)),
                     _stack_chunks, _split_pass, lambda s: s.n_pieces,
                     block=True),
    "tile": _Family("tile", ("tile_data", "tile_xcol", "tile_brow"),
                    lambda csr, ns: kops.tile_from_csr(csr), _stack_tiles,
                    _tile_pass, lambda t: 0),
}
assert tuple(_FAMILIES) == PLAN_KERNELS


def _build_stage(sub: CSRMatrix, kernel: str, shard: int, row_offset: int,
                 split_count: int = 0) -> ShardStage:
    """Shard ``shard``'s stage: its CSR ``sub``, whose first row is
    ``row_offset`` of the program's matrix, in ``kernel``'s format (a
    split stage asks for ``split_count`` splits, 0 = the policy)."""
    if kernel not in _FAMILIES:
        raise ValueError(f"unknown shard kernel {kernel!r}; expected one of "
                         f"{PLAN_KERNELS}")
    fam = _FAMILIES[kernel]
    return ShardStage(shard=shard, kernel=kernel, rows=sub.nrows,
                      row_offset=row_offset, nnz=sub.nnz,
                      **{fam.field: fam.build(sub, split_count)})


@dataclasses.dataclass
class SpmvProgram:
    """A lowered, device-ready SpMV program + its traffic accounting.

    This is the object every executor backend consumes (and what
    ``build_distributed`` has always returned — ``DistributedSpmv`` is a
    deprecated alias).  The legacy stacked-slab views (``data``/``cols``,
    ``seg_*``) are kept as lazily-built properties for old callers; new
    code should read ``stages``.
    """

    plan: SpmvPlan
    matrix: CSRMatrix                 # reordered matrix (host)
    partition: Partition
    x_layout: VectorLayout
    b_layout: VectorLayout
    rows_per_shard: np.ndarray        # true row counts (S,)
    row_offset: np.ndarray            # absolute first row per shard (S,)
    traffic: TrafficReport
    shard_traffic: np.ndarray         # (S, S) x-elements moved p<-q
    stages: tuple                     # (S,) ShardStage
    # Symmetric permutation applied by plan.reordering: perm[old] = new.
    # None for reordering="none"; the numpy executor uses it to accept and
    # return vectors in the caller's original index order.
    perm: np.ndarray | None = None

    def shard_kernels(self) -> tuple:
        """The per-shard kernels this program was lowered with."""
        return tuple(st.kernel for st in self.stages)

    def x_to_device(self, x: np.ndarray) -> np.ndarray:
        """x in the program's (reordered) index order -> layout shards;
        :func:`scatter_x` takes x in the caller's order."""
        return self.x_layout.to_sharded(x)

    def b_from_device(self, b_shards: np.ndarray) -> np.ndarray:
        return self.b_layout.from_sharded(b_shards)

    # -- legacy stacked-slab views (deprecated; read ``stages`` instead) ----

    @property
    def data(self) -> np.ndarray:
        """(S, rows_pad, W) stacked *uncapped* ELL slabs (legacy view)."""
        return self._ell_stack()[0]

    @property
    def cols(self) -> np.ndarray:
        """(S, rows_pad, W) stacked global ELL column ids (legacy view)."""
        return self._ell_stack()[1]

    def _ell_stack(self):
        cached = getattr(self, "_ell_stack_cache", None)
        if cached is not None:
            return cached
        slabs = []
        for st in self.stages:
            if st.kernel == "ell":
                slabs.append(st.ell)
            else:
                sub = self.matrix.row_slice(st.row_offset,
                                            st.row_offset + st.rows)
                slabs.append(csr_to_ell(sub))
        rows_pad = max(s.data.shape[0] for s in slabs)
        width = max(s.width for s in slabs)
        S = self.plan.num_shards
        data = np.zeros((S, rows_pad, width), dtype=np.float32)
        cols = np.zeros((S, rows_pad, width), dtype=np.int32)
        for p, s in enumerate(slabs):
            r, w = s.data.shape
            data[p, :r, :w] = s.data
            cols[p, :r, :w] = s.cols
        self._ell_stack_cache = (data, cols)
        return self._ell_stack_cache

    @property
    def seg_vals(self):
        s = self._seg_stack()
        return None if s is None else s["seg_vals"]

    @property
    def seg_cols(self):
        s = self._seg_stack()
        return None if s is None else s["seg_cols"]

    @property
    def seg_rows(self):
        s = self._seg_stack()
        return None if s is None else s["seg_rows"]

    @property
    def seg_pieces(self):
        s = self._seg_stack()
        return None if s is None else s["seg_pieces"]

    def _seg_stack(self):
        """Legacy stacked seg slabs (dummy-row piece padding), uniform-seg
        programs only — matches the pre-IR ``build_distributed`` contract."""
        if any(st.kernel != "seg" for st in self.stages):
            return None
        cached = getattr(self, "_seg_stack_cache", None)
        if cached is None:
            cached = _stack_seg_legacy([st.seg for st in self.stages],
                                       self.rows_per_shard)
            self._seg_stack_cache = cached
        return cached


def _stack_seg_legacy(segs, rows_per_shard) -> dict:
    """Stacked per-shard SegMatrix slabs, padded to common shapes.

    Column ids stay global (the allgather path gathers the full x); row ids
    are shard-local.  Piece padding targets the per-shard dummy row
    (``rows_pad``) with (lo=1, hi=0) so ``psum[c, hi] - psum[c, lo-1]``
    evaluates to an exact zero for padded entries.
    """
    S = len(segs)
    C_pad = max(s.num_chunks for s in segs)
    L = segs[0].chunk
    P_pad = max(max(s.n_pieces for s in segs), 1)
    rows_pad = int(np.asarray(rows_per_shard).max())
    vals = np.zeros((S, C_pad, L), dtype=np.float32)
    cols = np.zeros((S, C_pad, L), dtype=np.int32)
    rows = np.zeros((S, C_pad, L), dtype=np.int32)
    pieces = np.zeros((S, P_pad, 4), dtype=np.int32)
    pieces[:, :, 1] = 1                       # (lo=1, hi=0) -> exact zero
    pieces[:, :, 3] = rows_pad                # dummy row, sliced off later
    for p, s in enumerate(segs):
        vals[p, : s.num_chunks] = s.vals
        cols[p, : s.num_chunks] = s.cols
        rows[p, : s.num_chunks] = s.rows
        n = s.n_pieces
        pieces[p, :n, 0] = s.piece_chunk
        pieces[p, :n, 1] = s.piece_lo
        pieces[p, :n, 2] = s.piece_hi
        pieces[p, :n, 3] = s.piece_row
    return dict(seg_vals=vals, seg_cols=cols, seg_rows=rows,
                seg_pieces=pieces)


# --------------------------------------------------------------------------
# lowering
# --------------------------------------------------------------------------

def lower(csr: CSRMatrix, plan: SpmvPlan) -> SpmvProgram:
    """Lower (matrix, plan) to a per-shard-staged :class:`SpmvProgram`.

    The reordering permutation, partition, vector layouts and exact
    migration accounting are computed once here; each shard then gets the
    stage its (per-shard) kernel calls for.  ``plan.shard_kernels=None``
    lowers the uniform program (every stage uses ``plan.kernel``) — which
    is also how pre-per-shard plans deserialize from legacy JSON.  For
    ``split`` stages the split count comes from ``plan.split_counts`` (0
    or ``None`` = ask :func:`~repro.core.plan.split_meta`), clamped to
    the shard's chunk count.
    """
    if csr.nrows != csr.ncols:
        raise ValueError("paper applies symmetric reorderings to square "
                         "matrices")
    perm = None
    A = csr
    if plan.reordering != "none":
        perm = reordering_permutation(csr, plan.reordering, seed=plan.seed,
                                      parts=plan.num_shards)
        A = csr.permuted(perm, perm)
    part = make_partition(A, plan.num_shards, plan.distribution)
    x_layout = make_layout(plan.layout, A.ncols, plan.num_shards)
    b_layout = make_layout(plan.layout, A.nrows, plan.num_shards)
    kernels = plan.resolved_shard_kernels()
    split_counts = plan.resolved_split_counts()
    stages = tuple(_build_stage(part.shard_csr(A, p), kernels[p], p,
                                int(part.starts[p]), split_counts[p])
                   for p in range(plan.num_shards))
    return SpmvProgram(
        plan=plan, matrix=A, partition=part, x_layout=x_layout,
        b_layout=b_layout,
        rows_per_shard=part.rows_per_shard().astype(np.int64),
        row_offset=part.starts[:-1].astype(np.int64),
        traffic=count_migrations(A, part, x_layout, b_layout),
        shard_traffic=remote_access_matrix(A, part, x_layout),
        stages=stages, perm=perm)


#: Plan fields that force a full :func:`lower` when they change.  The
#: exchange (uniform or per-shard) is *not* one of them: stages, the
#: partition and the traffic accounting are exchange-independent — only
#: the executor's prologue and column remaps move, and those are rebuilt
#: lazily per program object — so an exchange flip relowers with every
#: stage shared (the rebalancer's cheapest partial move).
_BASE_FIELDS = ("layout", "distribution", "reordering", "num_shards", "seed")


def relower(program: SpmvProgram, new_plan: SpmvPlan) -> SpmvProgram:
    """Re-lower only the stages whose kernel (or effective split count)
    changed, keeping the same base.

    The base (layout / distribution / reordering / shards / seed) must
    match the incumbent plan — everything structural (matrix, partition,
    layouts, traffic) is shared, and unchanged stages are the *same
    objects* as the old program's.  Exchange policy changes (uniform or
    ``shard_exchanges``) share **all** stages: the exchange only selects
    the executor prologue.  This is what makes the serving rebalancer's
    hot-shard-only swap cheap: only the re-kerneled shards pay a slab
    rebuild, and the old program keeps serving until the new one
    validates.
    """
    old_plan = program.plan
    for f in _BASE_FIELDS:
        if getattr(new_plan, f) != getattr(old_plan, f):
            raise ValueError(
                f"relower only changes shard kernels; base field {f!r} "
                f"differs ({getattr(old_plan, f)!r} -> "
                f"{getattr(new_plan, f)!r}) — use lower()")
    old_k = old_plan.resolved_shard_kernels()
    new_k = new_plan.resolved_shard_kernels()
    new_sc = new_plan.resolved_split_counts()

    def unchanged(p: int) -> bool:
        if new_k[p] != old_k[p]:
            return False
        if new_k[p] != "split":
            return True
        # split stages also share when the *effective* (clamped/policy)
        # split count is unchanged — a different request that clamps to
        # the same NS must not trigger a rebuild.
        want = _resolved_split_count(
            program.partition.shard_csr(program.matrix, p), new_sc[p])
        return program.stages[p].split.num_splits == want

    stages = tuple(
        program.stages[p] if unchanged(p)
        else _build_stage(program.partition.shard_csr(program.matrix, p),
                          new_k[p], p, int(program.partition.starts[p]),
                          new_sc[p])
        for p in range(new_plan.num_shards))
    return dataclasses.replace(program, plan=new_plan, stages=stages)


# --------------------------------------------------------------------------
# numpy executor (exact host oracle; the correctness reference)
# --------------------------------------------------------------------------

def _apply_perm(v: np.ndarray, perm: np.ndarray) -> np.ndarray:
    """v in old order -> v in new order (perm[old] = new)."""
    out = np.empty_like(v)
    out[perm] = v
    return out


def _execute_numpy(program: SpmvProgram, x: np.ndarray) -> np.ndarray:
    """y = A @ x on one host, caller index order, float64.

    ``x`` may be a single (N,) vector or a multi-RHS block (N, B); the
    result matches ((M,) or (M, B)).  The block is held batch-major so
    every per-row reduction runs over the last *contiguous* axis
    regardless of B — numpy then applies the same pairwise-summation tree
    for every batch width, and the scatter formats (seg rows, hyb
    overflow) loop per RHS so ``np.add.at`` accumulates in identical
    index order per column.  Column b of a batched call is therefore
    *bitwise* equal to the per-vector call on ``x[:, b]``.
    """
    if x.shape[0] != program.matrix.ncols:
        raise ValueError(f"x has {x.shape[0]} elements, matrix expects "
                         f"{program.matrix.ncols}")
    if x.ndim == 1:
        return _execute_numpy_block(program, x[:, None])[:, 0]
    if x.ndim != 2:
        raise ValueError(f"x must be (N,) or (N, B), got shape {x.shape}")
    return _execute_numpy_block(program, x)


def _execute_numpy_block(program: SpmvProgram, x: np.ndarray) -> np.ndarray:
    B = x.shape[1]
    xr = x if program.perm is None else _apply_perm(x, program.perm)
    x_pad = np.zeros((B, program.x_layout.padded_length()), dtype=np.float64)
    x_pad[:, : program.matrix.ncols] = xr.T

    y = np.zeros((B, program.matrix.nrows), dtype=np.float64)
    for st in program.stages:
        if st.rows == 0:
            continue
        o, r = st.row_offset, st.rows
        if st.kernel == "seg":
            seg = st.seg
            contrib = seg.vals.astype(np.float64) * x_pad[:, seg.cols]
            yp = np.zeros((B, r))
            for b in range(B):            # padded slots: row 0, val 0
                np.add.at(yp[b], seg.rows, contrib[b])
            y[:, o:o + r] = yp
        elif st.kernel == "split":
            spl = st.split                # two-stage: partials, then combine
            contrib = spl.vals.astype(np.float64) * x_pad[:, spl.cols]
            s_ix = np.broadcast_to(
                np.arange(spl.num_splits)[:, None, None], spl.rows.shape)
            partial = np.zeros((B, spl.num_splits, r))
            for b in range(B):            # padded slots: row 0, val 0
                np.add.at(partial[b], (s_ix, spl.rows), contrib[b])
            y[:, o:o + r] = partial.sum(axis=1)
        elif st.kernel == "tile":
            tl = st.tile                  # dense tile stream, block scatter
            N = tl.shape[1]
            Nb = max(-(-N // tl.bn), 1)
            xw = np.zeros((B, Nb * tl.bn))
            xw[:, :N] = x_pad[:, :N]
            gathered = xw.reshape(B, Nb, tl.bn)[:, tl.tile_cols]  # (B,T,bn)
            # Contiguous last-axis reduction (like the ELL slab) keeps
            # column b of a batched call bitwise-equal to the per-vector
            # call; the per-b scatter then fixes the accumulation order.
            contrib = (tl.data.astype(np.float64)[None]
                       * gathered[:, :, None, :]).sum(axis=3)     # (B,T,bm)
            Mb = max(-(-r // tl.bm), 1)
            yp = np.zeros((B, Mb, tl.bm))
            for b in range(B):
                np.add.at(yp[b], tl.tile_rows, contrib[b])
            y[:, o:o + r] = yp.reshape(B, Mb * tl.bm)[:, :r]
        else:                             # "ell" / "hyb"
            e = st.ell
            slab = e.data.astype(np.float64) * x_pad[:, e.cols]
            y[:, o:o + r] = np.ascontiguousarray(slab).sum(axis=2)[:, :r]
            if e.overflow_vals.size:      # hyb COO tail
                ovals = e.overflow_vals.astype(np.float64)
                for b in range(B):
                    np.add.at(y[b], o + e.overflow_rows,
                              ovals * x_pad[b, e.overflow_cols])
    yt = y.T
    return yt if program.perm is None else yt[program.perm]


# --------------------------------------------------------------------------
# device executor: one shard_map for every program
# --------------------------------------------------------------------------

def _remote_reads(program: SpmvProgram) -> tuple:
    """The stored non-zeros that read a remote x entry under the
    program's layout (zero-valued entries excluded: they contribute
    nothing).  Returns ``(rows, keys)``: each such entry's row, and the
    sorted distinct ``reader * ncols + col`` keys of the (reader shard,
    global column) pairs they read."""
    A, part, lay = program.matrix, program.partition, program.x_layout
    rows_of_nnz = np.repeat(np.arange(A.nrows), np.diff(A.row_ptr))
    home = part.owner_of_rows(A.nrows)[rows_of_nnz]
    owners = lay.owner_of(A.col_index)
    rem = (A.values != 0) & (owners != home)
    keys = np.unique(home[rem].astype(np.int64) * A.ncols +
                     A.col_index[rem].astype(np.int64))
    return rows_of_nnz[rem], keys


def _halo_tables(program: SpmvProgram, reads: tuple | None = None):
    """Structure-level exchange tables (format-independent, per policy).

    For a reader p with exchange policy ``"halo"``, shard q sends exactly
    the x entries p's stored non-zeros read from q (zero-valued stored
    entries excluded — they contribute nothing, so they must not widen
    the halo).  For a reader with policy ``"allgather"`` (per-shard mixed
    programs), q sends *all* of its owned real columns — full replication
    for that shard, delivered through the same single ``all_to_all`` that
    serves the halo readers.  Returns ``(send_idx, pos_map, H)``:
    ``send_idx[q, p]`` are sender-local indices (padded to H) and
    ``pos_map[p, g]`` the augmented-buffer position of global id g on
    reader p (the buffer is ``[x_local ++ recv]``, ``per + q * H +
    slot``).  ``reads`` is :func:`_remote_reads` of the program, where
    the caller has it already.
    """
    A, part, lay = program.matrix, program.partition, program.x_layout
    S = part.num_shards
    per = lay.padded_length() // S
    policies = program.plan.resolved_shard_exchanges()
    _, uniq = _remote_reads(program) if reads is None else reads
    needed = [[np.zeros(0, np.int64)] * S for _ in range(S)]
    if uniq.size:                         # sorted: per reader, by global id
        up, ucol = uniq // A.ncols, uniq % A.ncols
        uq = lay.owner_of(ucol)
        for p in range(S):
            if policies[p] != "halo":
                continue
            for q in range(S):
                needed[p][q] = ucol[(up == p) & (uq == q)]
    if any(e == "allgather" for e in policies):
        col_owner = lay.owner_of(np.arange(A.ncols))
        owned = [np.flatnonzero(col_owner == q).astype(np.int64)
                 for q in range(S)]
        for p in range(S):
            if policies[p] == "allgather":
                for q in range(S):
                    if q != p:
                        needed[p][q] = owned[q]
    H = max(max((ids.size for row in needed for ids in row), default=1), 1)
    send_idx = np.zeros((S, S, H), dtype=np.int32)
    pos_map = np.zeros((S, A.ncols), dtype=np.int32)
    for p in range(S):
        for q in range(S):
            ids = needed[p][q]
            if ids.size:
                send_idx[q, p, : ids.size] = lay.local_index(ids)
                pos_map[p, ids] = per + q * H + np.arange(ids.size)
    return send_idx, pos_map, H


def _remap_cols(cols: np.ndarray, vals: np.ndarray, lay: VectorLayout,
                p: int, pos_map_p: np.ndarray) -> np.ndarray:
    """Global col ids -> positions in shard p's [x_local ++ recv] buffer.

    Zero-valued slots (padding, stored explicit zeros) keep position 0:
    x_local[0] times value 0 contributes nothing either way."""
    own = lay.owner_of(cols)
    out = np.where(own == p, lay.local_index(cols), 0).astype(np.int32)
    m = (own != p) & (vals != 0)
    if m.any():
        out[m] = pos_map_p[cols[m]]
    return out


def _row_remote_flags(program: SpmvProgram,
                      reads: tuple | None = None) -> np.ndarray:
    """(nrows,) bool — rows with >= 1 stored non-zero reading a remote x
    entry under the program's layout.  These are the rows whose partial
    products must wait for the exchange; every other row is computable
    from ``x_local`` alone (the pipelined executor's local slice).
    ``reads`` is :func:`_remote_reads` of the program, where the caller
    has it already."""
    rows, _ = _remote_reads(program) if reads is None else reads
    flags = np.zeros(program.matrix.nrows, dtype=bool)
    flags[rows] = True
    return flags


def _row_masked_csr(sub: CSRMatrix, keep: np.ndarray) -> CSRMatrix:
    """Same-shape CSR with the entries of non-kept rows dropped.

    Row count (and shard-local row ids) are preserved so the masked
    stage scatters into the same (R,) output as the full stage; only the
    masked-out rows lower to empty rows."""
    if keep.all():
        return sub
    per_row = np.diff(sub.row_ptr)
    rows = np.repeat(np.arange(sub.nrows), per_row)
    m = keep[rows]
    counts = np.bincount(rows[m], minlength=sub.nrows)
    row_ptr = np.zeros(sub.nrows + 1, dtype=np.int64)
    np.cumsum(counts, out=row_ptr[1:])
    return CSRMatrix(shape=sub.shape, values=sub.values[m],
                     col_index=sub.col_index[m], row_ptr=row_ptr)


def _device_operands(program: SpmvProgram) -> dict:
    """Build the pipelined executor's operand sets (cached on the program).

    Each shard's kernel work is split by row into a **local slice**
    (rows reading only columns the shard owns — runnable from
    ``x_local`` before any communication) and a **remote slice** (rows
    with at least one halo-dependent read — combined when the exchange
    lands).  Both slices are lowered into the shard's own kernel family
    and stacked into two uniform-shape operand sets (``loc_*`` /
    ``rem_*``); ``row_remote`` selects, per output row, which pass owns
    the result.  Column ids in the local set are pre-remapped to
    ``x_local`` positions; the remote set's ids target the exchange
    buffer (``[x_local ++ recv]`` for any program with a halo reader,
    the gathered global x for uniform all-gather).

    A set holds only ``set_keys``, the formats of ``families``: the
    kernel families the program's stages run, in ``PLAN_KERNELS`` order.
    Each slab is stacked once over all shards, zeros where a shard runs
    another family.  ``kid``, each shard's index into ``families``,
    exists only where there are several.

    A program in which no row of any shard reads a remote entry (every
    one-shard program, and block-diagonal ones under their layout) has
    an empty remote slice: it stacks ``program.stages`` as lowered, as
    its local set alone, with no ``rem_*``, ``send_idx`` or
    ``row_remote``.  ``passes`` is the number of kernel passes the step
    runs per vector (1 or 2).

    ``exchange`` counts what the second pass adds, from the tables built
    here: ``sent_entries``, the x entries one vector's collective moves,
    padded as sent (S·S·H for the all-to-all, S·S·per for the
    all-gather); ``needed_entries``, the distinct (reader shard, column)
    pairs the stored non-zeros read remotely, unpadded; and per shard
    ``remote_rows`` (rows with a remote read), ``remote_pass_rows`` (rows
    the remote pass computes, ``R``) and ``shard_nnz`` (stored entries).
    Every count of a one-pass program is 0.

    ``kernels["scatter_updates"]`` counts, per shard, the scatter-add
    updates one vector's step runs, summed over its passes
    (``_Family.scatter_updates``).
    """
    cached = getattr(program, "_device_ops_cache", None)
    if cached is not None:
        return cached
    S = program.plan.num_shards
    stages = program.stages
    lay = program.x_layout
    R = int(max(round_up(max(st.rows, 1), ELL_SUBLANE) for st in stages))
    families = tuple(k for k in PLAN_KERNELS
                     if any(st.kernel == k for st in stages))
    set_keys = tuple(dict.fromkeys(k for f in families
                                   for k in _FAMILIES[f].keys))
    reads = _remote_reads(program)
    flags = _row_remote_flags(program, reads)
    remote = bool(flags.any())
    use_a2a = any(e == "halo" for e in program.plan.resolved_shard_exchanges())

    if remote and use_a2a:
        send_idx, pos_map, _ = _halo_tables(program, reads)
    else:
        send_idx = np.zeros((S, 1, 1), dtype=np.int32)

    def remap_rem(cols, vals, p):
        if not use_a2a:
            return cols.astype(np.int32)
        return _remap_cols(cols, vals, lay, p, pos_map[p])

    def remap_loc(cols, vals, p):
        # Local-slice entries only read columns owned by p; zero-valued
        # (padding) slots keep position 0 — x_local[0] times 0 is 0.
        out = lay.local_index(cols).astype(np.int32)
        return np.where(vals != 0, out, 0).astype(np.int32)

    def stack_set(set_stages, remap):    # -> (set, the split pass's NS)
        out = {}
        for stack in dict.fromkeys(_FAMILIES[f].stack for f in families):
            out.update(stack([st.payload if _FAMILIES[st.kernel].stack is stack
                              else None for st in set_stages], R, remap))
        return {k: out[k] for k in set_keys}, out.get("NS", 1)

    row_remote = np.zeros((S, R), dtype=bool)
    loc_stages, rem_stages = list(stages), []
    if remote:
        for p, st in enumerate(stages):
            rr = flags[st.row_offset: st.row_offset + st.rows]
            row_remote[p, : st.rows] = rr
            sub = program.partition.shard_csr(program.matrix, p)
            # each row slice in the stage's own family and split count
            ns = getattr(st.payload, "num_splits", 0)
            loc_stages[p] = _build_stage(_row_masked_csr(sub, ~rr),
                                         st.kernel, p, st.row_offset, ns)
            rem_stages.append(_build_stage(_row_masked_csr(sub, rr),
                                           st.kernel, p, st.row_offset, ns))
    scatter = [0] * S                    # summed over both slices
    for p, st in enumerate(loc_stages + rem_stages):
        scatter[p % S] += _FAMILIES[st.kernel].scatter_updates(st.payload)
    loc, ns_loc = stack_set(loc_stages, remap_loc)
    cached = dict(families=families, set_keys=set_keys, R=R, passes=1,
                  NS_loc=ns_loc,
                  exchange=dict(sent_entries=0, needed_entries=0,
                                remote_rows=[0] * S,
                                remote_pass_rows=[0] * S, shard_nnz=[0] * S),
                  kernels=dict(scatter_updates=scatter))
    if len(families) > 1:
        cached["kid"] = np.array([families.index(st.kernel)
                                  for st in stages], dtype=np.int32)
    cached.update({"loc_" + k: v for k, v in loc.items()})
    if remote:
        rem, ns_rem = stack_set(rem_stages, remap_rem)
        per = lay.padded_length() // S
        cached.update(passes=2, NS_rem=ns_rem, send_idx=send_idx,
                      row_remote=row_remote, exchange=dict(
                          sent_entries=int(send_idx.size) if use_a2a
                          else S * S * per,
                          needed_entries=int(reads[1].size),
                          remote_rows=[int(n) for n in
                                       row_remote.sum(axis=1)],
                          remote_pass_rows=[R] * S,
                          shard_nnz=[int(st.nnz) for st in stages]))
        cached.update({"rem_" + k: v for k, v in rem.items()})
    program._device_ops_cache = cached
    return cached


def _operand_keys(ops: dict) -> tuple:
    """The step's operand names in argument order: ``kid`` where the
    program runs several families, the local set, then, for a two-pass
    program, the remote set and the exchange tables."""
    keys = ("kid",) if "kid" in ops else ()
    keys += tuple("loc_" + k for k in ops["set_keys"])
    if ops["passes"] == 2:
        keys += (tuple("rem_" + k for k in ops["set_keys"])
                 + ("send_idx", "row_remote"))
    return keys


def kernel_interpret(platform: str) -> bool:
    """Pallas interpret mode for the devices of ``platform``.

    Interpret mode is the CPU stand-in for the chip and nothing else: it
    is on exactly when the platform is ``"cpu"``.  The executor derives
    it from its mesh and takes no override, so a kernel meant for the
    chip never falls back to the interpreter in silence."""
    return platform == "cpu"


def _block_families(families, use_kernel: bool) -> dict:
    """Each family -> whether the step hands it a multi-RHS buffer whole
    (``_Family.block``); the Pallas passes take one vector at a time."""
    return {f: _FAMILIES[f].block and not use_kernel for f in families}


def _mesh_platform(mesh) -> str:
    return mesh.devices.flat[0].platform


def build_program_step(program: SpmvProgram, mesh, axis: str = "model", *,
                       use_kernel: bool = False, pipeline: bool = True):
    """The device executor's jitted step and its host operands.

    Returns ``(step, operands)``: ``step(*operands, x_shards)`` is the one
    jitted ``shard_map`` program, and ``operands`` are the host arrays in
    ``_operand_keys`` order, each with the shard axis first.  A program
    with no remote reads steps through its local slice alone: no
    exchange, no remote pass, no combine.  Nothing is placed on a device
    here, so the step can be lowered from shapes alone
    (``step.lower(*shape_structs)``) for a mesh of described devices.
    :func:`make_program_spmv_fn` is the serving wrapper around it.
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    S = program.plan.num_shards
    if axis not in mesh.shape:
        raise ValueError(f"mesh has no axis {axis!r} (axes "
                         f"{tuple(mesh.shape)})")
    if mesh.shape[axis] != S:
        raise ValueError(
            f"the program has {S} shards but the mesh has "
            f"{mesh.shape[axis]} devices along {axis!r}: lower the plan "
            f"with num_shards={mesh.shape[axis]}")
    interpret = kernel_interpret(_mesh_platform(mesh))
    ops = _device_operands(program)
    R = ops["R"]
    keys = _operand_keys(ops)
    use_a2a = any(e == "halo" for e in program.plan.resolved_shard_exchanges())
    kind = program.x_layout.kind
    families, set_keys = ops["families"], ops["set_keys"]
    block = _block_families(families, use_kernel)

    def _to_global(x_all):
        """(S, per[, B]) gathered shards -> global (padded) order."""
        if kind == "block":
            return x_all.reshape((-1,) + x_all.shape[2:])
        return jnp.swapaxes(x_all, 0, 1).reshape((-1,) + x_all.shape[2:])

    def shard_fn(*args):
        *args, x_shard = args
        op = {k: a[0] for k, a in zip(keys, args)}    # this shard's operands

        def kernel_pass(prefix, num_splits, xv):
            """One slice's kernel pass against its own x buffer: its
            family's pass, or a ``lax.switch`` over the program's families
            where it runs several, each under the family's name.

            A multi-RHS buffer (n, B) goes whole to the chunk families'
            jnp pass (``_Family.block``), which gathers x once a request;
            the other families, and every Pallas pass, run one vector at a
            time in a device loop, reading the slice's operands B times."""
            a = {k: op[prefix + k] for k in set_keys}

            def family_pass(f):
                run = jax.named_scope(f)(partial(
                    _FAMILIES[f].run, a, num_rows=R, num_splits=num_splits,
                    use_kernel=use_kernel, interpret=interpret))
                if xv.ndim == 2 and not block[f]:
                    return lambda: jax.lax.map(run, xv.T).T
                return lambda: run(xv)
            passes = [family_pass(f) for f in families]
            if len(passes) == 1:
                return passes[0]()
            return jax.lax.switch(op["kid"], passes)

        x_local = x_shard[0]                               # (per[, B])
        if ops["passes"] == 1:          # no remote reads: y is y_loc
            y = jax.named_scope("spmv.local")(kernel_pass)(
                "loc_", ops["NS_loc"], x_local)
            return y[None]
        with jax.named_scope("spmv.exchange"):
            if use_a2a:
                to_send = jnp.take(x_local, op["send_idx"], axis=0)
                recv = jax.lax.all_to_all(to_send, axis, split_axis=0,
                                          concat_axis=0, tiled=True)
                xg = jnp.concatenate(
                    [x_local, recv.reshape((-1,) + recv.shape[2:])], axis=0)
            else:
                x_all = jax.lax.all_gather(x_local, axis)  # (S, per[, B])
                xg = _to_global(x_all)

        x_loc_in = x_local
        if not pipeline:
            # Serial order: tie the local pass's input to the completed
            # exchange so no kernel work precedes the collective.  The
            # values are untouched — identical operands, identical
            # combine — so serial and pipelined runs are bitwise-equal;
            # only the scheduling freedom differs.
            x_loc_in, _ = jax.lax.optimization_barrier((x_local, xg))

        y_loc = jax.named_scope("spmv.local")(kernel_pass)(
            "loc_", ops["NS_loc"], x_loc_in)
        y_rem = jax.named_scope("spmv.remote")(kernel_pass)(
            "rem_", ops["NS_rem"], xg)
        with jax.named_scope("spmv.combine"):
            m = op["row_remote"]
            if y_rem.ndim == 2:                            # batched (R, B)
                m = m[:, None]
            y = jnp.where(m, y_rem, y_loc)
        return y[None]

    # check_vma off: pallas_call has no replication rule.
    fn = jax.shard_map(shard_fn, mesh=mesh,
                       in_specs=(P(axis),) * (len(keys) + 1),
                       out_specs=P(axis), check_vma=False)
    return jax.jit(fn), tuple(ops[k] for k in keys)


def make_program_spmv_fn(program: SpmvProgram, mesh, axis: str = "model", *,
                         use_kernel: bool = False, pipeline: bool = True,
                         phases: dict | None = None):
    """THE device executor: one shard_map function for any lowered program.

    Returns ``f(x_shards) -> y_shards`` with ``x_shards`` of shape
    (S, per_shard) or batched (S, per_shard, B) in layout order (see
    :meth:`SpmvProgram.x_to_device`), and ``y_shards`` of shape
    (S, rows_pad[, B]) (slice each shard to its true ``rows_per_shard``,
    or use :func:`gather_b`).  The mesh must have ``plan.num_shards``
    devices along ``axis``; the program's operands are placed on them
    once, split along the shard axis, here.  ``f.program`` is the program
    it serves, ``f.operands`` its placed device operands,
    ``f.operand_bytes`` their bytes, ``f.passes`` the kernel passes it
    runs per vector, ``f.exchange`` the counters of what its second
    pass adds, ``f.kernel_counts`` the scatter-add updates each shard
    runs a vector (:func:`_device_operands`), and ``f.operand_reads(B)``
    the operand-set reads one request of B vectors costs a shard:
    ``passes`` where every family of the program takes the block whole,
    else ``passes * B``.

    The exchange prologue
    follows ``plan.resolved_shard_exchanges()``: uniform all-gather when
    every shard picks ``allgather``, otherwise one all-to-all whose
    per-reader payload is the exact halo (``halo`` shards) or the full
    replication (``allgather`` shards).  Each shard runs its stage's
    kernel (``ell`` / ``seg`` / ``hyb`` / ``split`` / ``tile``): directly
    where every shard runs one family, through a ``lax.switch`` over the
    program's families otherwise — one SPMD program, heterogeneous
    per-shard execution.

    The schedule is **pipelined** (the ROADMAP item-4 executor): each
    shard's kernel work is pre-split by row into a local slice whose
    pass reads only ``x_local`` — issuable while the collective is in
    flight — and a remote slice whose pass waits for the exchange
    buffer; ``row_remote`` selects per row which pass owns the result.
    ``pipeline=False`` runs the *same* two passes behind an
    ``optimization_barrier`` that ties the local pass's input to the
    completed exchange — the pre-pipeline serial order, bitwise-equal
    output by construction (identical operands and combine, scheduling
    freedom removed).  A program in which no row reads a remote entry
    has no remote slice (:func:`_device_operands`): it runs one pass,
    with no exchange, and ``pipeline`` has nothing to order in it.

    ``use_kernel=True`` runs the Pallas kernels, compiled on the chip and
    interpreted on the CPU (:func:`kernel_interpret`); the default runs
    the pure-jnp oracles.

    Building the operand sets and placing them are the spans
    ``ingest.stack`` and ``ingest.place``, counted into ``phases`` where
    it is given (:func:`repro.core.spans.span`).
    """
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    with span("ingest.stack", phases):
        ops = _device_operands(program)
    step, host_ops = build_program_step(
        program, mesh, axis, use_kernel=use_kernel, pipeline=pipeline)
    sharding = NamedSharding(mesh, P(axis))
    with span("ingest.place", phases):
        operands = jax.device_put(host_ops, sharding)

    def run(x_shards):
        return step(*operands, jax.device_put(x_shards, sharding))

    run.program = program
    run.operands = operands
    run.operand_bytes = int(sum(a.nbytes for a in host_ops))
    run.passes = ops["passes"]
    whole = all(_block_families(ops["families"], use_kernel).values())
    run.operand_reads = lambda batch: ops["passes"] * (1 if whole else batch)
    run.exchange = ops["exchange"]
    run.kernel_counts = ops["kernels"]
    return run


def scatter_x(program: SpmvProgram, x: np.ndarray) -> np.ndarray:
    """Caller-order x ((N,) or (N, B)) -> (S, per[, B]) float32 shards in
    layout order: the device executor's input, and the inverse of
    :func:`gather_b` (which undoes the reordering on the way out)."""
    x = np.asarray(x, dtype=np.float32)
    if program.perm is not None:
        x = _apply_perm(x, program.perm)
    return program.x_to_device(x)


def gather_b(program: SpmvProgram, y_shards) -> np.ndarray:
    """(S, rows_pad[, B]) device output -> global b in the caller's order."""
    y = np.asarray(y_shards)
    out = np.zeros((program.matrix.nrows,) + y.shape[2:], dtype=y.dtype)
    for p, st in enumerate(program.stages):
        out[st.row_offset: st.row_offset + st.rows] = y[p, : st.rows]
    return out if program.perm is None else out[program.perm]


# --------------------------------------------------------------------------
# Emu probe backend + the one executor entry point
# --------------------------------------------------------------------------

def probe_program(program: SpmvProgram, *, emu: EmuConfig | None = None,
                  engine: str = "vectorized") -> EmuResult:
    """Run the Emu timeline simulator on the program's (matrix, partition,
    layout) walk — the migratory-thread cost of the same plan the other
    backends execute.  This is the probe the autotuner's re-ranking and
    the rebalancer's drift oracle consume."""
    emu = emu or EmuConfig(nodelets=program.plan.num_shards)
    return run_spmv(program.matrix, program.partition, program.x_layout,
                    emu, engine=engine)


def execute(program: SpmvProgram, x: np.ndarray | None = None, *,
            backend: str = "numpy", mesh=None, axis: str = "model",
            use_kernel: bool = False, pipeline: bool = True,
            emu: EmuConfig | None = None, engine: str = "vectorized"):
    """Execute a lowered program — the single entry point for every backend.

    * ``backend="numpy"``: exact float64 host oracle; returns y in the
      caller's index order ((M,) or (M, B) for batched x).
    * ``backend="shard_map"``: the device executor (requires ``mesh`` with
      ``plan.num_shards`` devices along ``axis``); builds the one-shot
      :func:`make_program_spmv_fn`, runs it, and assembles the caller-order
      result — use ``make_program_spmv_fn`` directly for a reusable
      compiled function.  ``pipeline=False`` forces the pre-pipeline
      serial schedule (exchange completes before any kernel work) —
      bitwise-equal to the default pipelined schedule.
    * ``backend="emu"``: ignores ``x`` and returns the
      :class:`~repro.core.emu.EmuResult` timeline probe.
    """
    if backend == "emu":
        return probe_program(program, emu=emu, engine=engine)
    if x is None:
        raise ValueError(f"backend {backend!r} needs an input vector x")
    if backend == "numpy":
        return _execute_numpy(program, x)
    if backend == "shard_map":
        if mesh is None:
            raise ValueError("backend='shard_map' needs a mesh with "
                             "plan.num_shards devices")
        fn = make_program_spmv_fn(program, mesh, axis=axis,
                                  use_kernel=use_kernel, pipeline=pipeline)
        return gather_b(program, fn(scatter_x(program, x)))
    raise ValueError(f"unknown executor backend {backend!r}; expected "
                     f"'numpy', 'shard_map', or 'emu'")
