"""Distributed SpMV: the paper's optimization axes as one plan object.

``SpmvPlan`` is the first-class configuration: layout x distribution x
reordering x exchange x kernel — exactly the paper's study grid, plus the
per-shard ``shard_kernels`` axis (each shard independently ``ell`` /
``seg`` / ``hyb``) that the per-region selection literature argues for.

Since the SpmvProgram refactor the *lowering and execution* live in
:mod:`repro.core.program`: ``lower(csr, plan)`` produces the per-shard
staged program and ``execute`` / ``make_program_spmv_fn`` are the single
executor entry points (numpy oracle, one shard_map device program, Emu
probe).  This module keeps the plan itself, the halo-exchange accounting
(:func:`build_halo`), and thin **deprecated shims** for the pre-IR API:
``build_distributed``, ``local_spmv``, ``make_spmv_fn``,
``make_seg_spmv_fn``, ``make_halo_spmv_fn`` — all of which now delegate to
the one program executor.

* ``allgather``  — every device gathers the full x then gathers locally;
                   the Hein et al. baseline the paper contrasts against
                   (x replicated), maximal ICI bytes, zero imbalance.
* ``halo``       — each device fetches only the x shards it actually reads
                   (block layout + reordered matrices make this cheap); the
                   faithful analogue of migratory access.
"""
from __future__ import annotations

import dataclasses
import warnings
from typing import Literal

import jax
import numpy as np
from jax.sharding import Mesh

from .sparse_matrix import CSRMatrix

__all__ = ["SpmvPlan", "DistributedSpmv", "build_distributed",
           "make_spmv_fn", "make_seg_spmv_fn", "build_halo",
           "make_halo_spmv_fn", "local_spmv"]

#: Kernel spellings a plan accepts (per-shard or uniform), in tie-break
#: preference order (the regular ELL stream wins ties against formats that
#: pay scan/scatter overheads; the dense-tile stream comes last — it only
#: wins when the blocked structure makes it *strictly* cheaper).  The
#: SINGLE definition: ``plan.KERNELS`` (selector/majority order) aliases
#: it, and ``program._FAMILIES`` (each family's device format) holds one
#: entry per kernel in this order, so the layers cannot drift.  New
#: families are appended, never inserted.
PLAN_KERNELS = ("ell", "seg", "hyb", "split", "tile")

#: Exchange policies a plan accepts (uniform or per-shard).  ``halo``
#: first: on a cost tie the exact-entries exchange wins over full
#: replication.  Single definition — ``plan.select_shard_exchanges`` and
#: the executor's prologue both read this tuple.
PLAN_EXCHANGES = ("halo", "allgather")


@dataclasses.dataclass(frozen=True)
class SpmvPlan:
    """The paper's optimization grid as one config object.

    ``distribution="nnz"`` is the nonzero-balanced split (alias of
    ``"nonzero"``): device row-ranges are chosen by cumulative-nnz split
    instead of equal rows, so a power-law matrix cannot converge all the
    work on one device the way it converges threads on one nodelet in the
    paper's §IV-D.  ``kernel`` picks the per-shard device format:
    ``"ell"`` (row-tiled padded slabs), ``"seg"`` (nonzero-balanced
    segmented chunks whose *grid* is load-balance-aware too), ``"hyb"``
    (p95-capped ELL + COO overflow tail for skew-tolerant padding),
    ``"split"`` (split-nnz two-stage split-K: the seg chunk grid cut into
    NS partial accumulators plus a tiny combine — the monster-row cure),
    or ``"tile"`` (bitmask-tiled: a coarse pointer grid over dense
    (8, 128) tiles streamed with whole-tile FMAs and no per-element
    column indices — the blocked answer for banded/block matrices).

    ``shard_kernels`` (optional) overrides the kernel **per shard** — one
    entry per shard, each in :data:`PLAN_KERNELS` — producing the
    heterogeneous programs the per-shard autotuner emits for
    mixed-structure matrices.  ``None`` (the default, and what legacy
    JSON without the field deserializes to) means the uniform program:
    every shard uses ``kernel``.  ``split_counts`` (optional) pins the
    per-shard split count NS for ``split`` shards — one entry per shard,
    ignored (must be 1 or None-like) on non-split shards; ``None`` means
    the lowering asks ``plan.split_meta`` (the occupancy-driven
    ``get_meta_param`` analogue) per shard.

    ``shard_exchanges`` (optional) overrides the exchange **per shard**
    — one entry per shard, each in :data:`PLAN_EXCHANGES`.  A skewed
    shard that reads most of x pays less streaming the full replication
    (``allgather``) than assembling a near-total halo; a banded shard
    keeps the exact-entries ``halo``.  ``None`` (the default, and what
    legacy JSON deserializes to) means every shard uses ``exchange``.
    Plans remain frozen, hashable and JSON-round-trippable either way.
    """

    layout: Literal["block", "cyclic"] = "block"
    distribution: Literal["row", "nonzero", "nnz"] = "nonzero"
    reordering: Literal["none", "random", "bfs", "metis", "degree"] = "none"
    exchange: Literal["allgather", "halo"] = "halo"
    kernel: Literal["ell", "seg", "hyb", "split", "tile"] = "ell"
    num_shards: int = 8
    seed: int = 0
    shard_kernels: tuple | None = None
    split_counts: tuple | None = None
    shard_exchanges: tuple | None = None

    def __post_init__(self):
        if self.shard_kernels is not None:
            sk = tuple(self.shard_kernels)   # JSON lists -> hashable tuple
            bad = [k for k in sk if k not in PLAN_KERNELS]
            if bad:
                raise ValueError(f"unknown shard kernel(s) {bad!r}; expected "
                                 f"entries from {PLAN_KERNELS}")
            object.__setattr__(self, "shard_kernels", sk)
        if self.split_counts is not None:
            sc = tuple(int(c) for c in self.split_counts)
            if any(c < 1 for c in sc):
                raise ValueError(f"split_counts must be >= 1, got {sc!r}")
            object.__setattr__(self, "split_counts", sc)
        if self.shard_exchanges is not None:
            se = tuple(self.shard_exchanges)  # JSON lists -> hashable tuple
            bad = [e for e in se if e not in PLAN_EXCHANGES]
            if bad:
                raise ValueError(f"unknown shard exchange(s) {bad!r}; "
                                 f"expected entries from {PLAN_EXCHANGES}")
            object.__setattr__(self, "shard_exchanges", se)

    def resolved_shard_kernels(self) -> tuple:
        """The per-shard kernel tuple this plan lowers to (length S)."""
        if self.shard_kernels is None:
            return (self.kernel,) * self.num_shards
        if len(self.shard_kernels) != self.num_shards:
            raise ValueError(
                f"shard_kernels has {len(self.shard_kernels)} entries but "
                f"num_shards={self.num_shards}")
        return self.shard_kernels

    def resolved_shard_exchanges(self) -> tuple:
        """The per-shard exchange tuple this plan executes with (length S)."""
        if self.shard_exchanges is None:
            return (self.exchange,) * self.num_shards
        if len(self.shard_exchanges) != self.num_shards:
            raise ValueError(
                f"shard_exchanges has {len(self.shard_exchanges)} entries "
                f"but num_shards={self.num_shards}")
        return self.shard_exchanges

    def resolved_split_counts(self) -> tuple:
        """Per-shard split-count requests (length S; 0 = let the policy
        decide).  Entries only matter for shards lowered as ``split``."""
        if self.split_counts is None:
            return (0,) * self.num_shards
        if len(self.split_counts) != self.num_shards:
            raise ValueError(
                f"split_counts has {len(self.split_counts)} entries but "
                f"num_shards={self.num_shards}")
        return self.split_counts

    def retarget(self, num_shards: int) -> "SpmvPlan":
        """Re-target to a different shard count.

        Per-shard kernel/split/exchange tuples are only meaningful for
        the shard count they were tuned on, so a mismatched
        ``shard_kernels`` (or ``split_counts``, or ``shard_exchanges``)
        is dropped (the plan falls back to its uniform ``kernel`` / the
        split policy / its uniform ``exchange``) instead of producing an
        unlowerable plan.
        """
        sk = self.shard_kernels
        if sk is not None and len(sk) != num_shards:
            sk = None
        sc = self.split_counts
        if sc is not None and len(sc) != num_shards:
            sc = None
        se = self.shard_exchanges
        if se is not None and len(se) != num_shards:
            se = None
        return dataclasses.replace(self, num_shards=num_shards,
                                   shard_kernels=sk, split_counts=sc,
                                   shard_exchanges=se)

    @classmethod
    def auto(cls, csr: CSRMatrix, *, num_shards: int = 8, seed: int = 0,
             probe: int | str | None = None, **grid) -> "SpmvPlan":
        """Pick a plan for ``csr`` with the cost-model autotuner.

        Thin wrapper over :func:`repro.core.plan.autotune` (which see for
        the candidate grid — including per-shard kernel selection — and
        the ``probe`` refinement: simulator re-ranking of the top
        ``plan.DEFAULT_PROBE`` bases unless overridden; ``probe="auto"``
        probes adaptively until the measured-vs-analytic inversion rate
        stabilizes); returns only the winning plan.  Use ``autotune``
        directly when the full ranking or the JSON-serializable
        :class:`~repro.core.plan.PlanChoice` is needed (the serving
        engine persists it per ingested matrix).
        """
        from .plan import autotune
        return autotune(csr, num_shards=num_shards, seed=seed, probe=probe,
                        **grid).plan



#: Shims that already warned this process — each deprecated ``make_*`` shim
#: emits its DeprecationWarning exactly once, so a tight legacy serving
#: loop is not spammed while migration off the pre-IR API is in flight.
_DEPRECATION_WARNED: set = set()


def _warn_deprecated(name: str, replacement: str) -> None:
    if name in _DEPRECATION_WARNED:
        return
    _DEPRECATION_WARNED.add(name)
    warnings.warn(
        f"{name} is deprecated; use {replacement} instead",
        DeprecationWarning, stacklevel=3)


def build_distributed(csr: CSRMatrix, plan: SpmvPlan):
    """Deprecated alias of :func:`repro.core.program.lower`."""
    from .program import lower
    return lower(csr, plan)


def local_spmv(dist, x: np.ndarray) -> np.ndarray:
    """Single-host execution of a lowered program: y = A @ x, caller order.

    Deprecated alias of ``program.execute(dist, x, backend="numpy")`` —
    the exact float64 oracle every serving request runs through
    (``serve.engine.SparseMatrixEngine``).  ``x`` may be a single (N,)
    vector or a multi-RHS block (N, B); column b of a batched call is
    *bitwise* equal to the per-vector call on ``x[:, b]``.
    """
    from .program import execute
    return execute(dist, x, backend="numpy")


def make_spmv_fn(dist, mesh: Mesh, axis: str = "model",
                 *, use_kernel: bool = False):
    """Deprecated shim over :func:`repro.core.program.make_program_spmv_fn`.

    Returns the old ``f(data, cols, x_shards) -> b_shards`` signature; the
    slab arguments are accepted for compatibility but the program's own
    lowered operands (identical content) are what execute.  Matching the
    historical factory, the exchange is always all-gather — a halo plan is
    re-bound (stages shared) first; use
    :func:`~repro.core.program.make_program_spmv_fn` for plan-driven
    exchange selection.
    """
    _warn_deprecated("make_spmv_fn", "repro.core.program.make_program_spmv_fn")
    from .program import make_program_spmv_fn
    prog = dist
    if prog.plan.exchange != "allgather" or prog.plan.shard_exchanges:
        prog = lower_with_exchange(
            prog, dataclasses.replace(prog.plan, exchange="allgather",
                                      shard_exchanges=None))
    inner = make_program_spmv_fn(prog, mesh, axis=axis,
                                 use_kernel=use_kernel)

    @jax.jit
    def fn(data, cols, x_shards):
        del data, cols                      # the program carries its slabs
        return inner(x_shards)
    return fn


def make_seg_spmv_fn(dist, mesh: Mesh, axis: str = "model",
                     *, use_kernel: bool = False):
    """Deprecated shim over :func:`repro.core.program.make_program_spmv_fn`
    for uniform-seg programs (old ``f(vals, cols, rows, pieces, x_shards)``
    signature)."""
    _warn_deprecated("make_seg_spmv_fn",
                     "repro.core.program.make_program_spmv_fn")
    if any(st.kernel != "seg" for st in dist.stages):
        raise ValueError("build_distributed was not run with plan.kernel='seg'")
    from .program import make_program_spmv_fn
    prog = dist
    if prog.plan.exchange != "allgather" or prog.plan.shard_exchanges:
        # historical factory: uniform all-gather
        prog = lower_with_exchange(
            prog, dataclasses.replace(prog.plan, exchange="allgather",
                                      shard_exchanges=None))
    inner = make_program_spmv_fn(prog, mesh, axis=axis,
                                 use_kernel=use_kernel)
    rows_pad = int(dist.rows_per_shard.max())

    @jax.jit
    def fn(vals, cols, rows, pieces, x_shards):
        del vals, cols, rows, pieces
        return inner(x_shards)[:, :rows_pad]
    return fn


def _apply_perm(v: np.ndarray, perm: np.ndarray) -> np.ndarray:
    """v in old order -> v in new order (perm[old] = new)."""
    out = np.empty_like(v)
    out[perm] = v
    return out


# --------------------------------------------------------------------------
# halo exchange accounting — the migratory-access analogue (beyond the
# all-gather baseline, which is the Hein et al. x-replication the paper
# contrasts).  The executor's halo prologue lives in core/program.py; this
# host-side builder remains the ICI-bytes accounting surface
# (benchmarks/spmv_exchange.py) and the legacy shim's operand source.
# --------------------------------------------------------------------------

@dataclasses.dataclass
class HaloProgram:
    """Host-precomputed halo exchange for one lowered program.

    Shard q sends to shard p exactly the x entries p's rows read from q
    (``send_idx[q, p]``, padded to the max halo H).  On device one
    ``all_to_all`` moves S*H elements per shard instead of the full vector;
    the ELL column ids are remapped into [local_x ++ recv_buffer].
    """

    send_idx: np.ndarray      # (S, S, H) local indices on the sender
    cols_remap: np.ndarray    # (S, rows_pad, W) into the augmented buffer
    halo: int                 # H
    comm_elems_per_shard: int  # S * H (vs padded_length for all-gather)


def build_halo(dist) -> HaloProgram:
    S = dist.plan.num_shards
    lay = dist.x_layout
    per = lay.padded_length() // S
    # Padded ELL slots (and stored explicit zeros) carry value 0 and point
    # at col 0; they contribute nothing to y, so they must not widen the
    # halo — otherwise every shard p != 0 appears to read global id 0 from
    # shard 0 and H (hence comm_elems_per_shard) is inflated.
    needed = [[None] * S for _ in range(S)]
    for p in range(S):
        cols_p = dist.cols[p].reshape(-1)
        act_p = dist.data[p].reshape(-1) != 0
        own_p = lay.owner_of(cols_p)
        for q in range(S):
            ids = np.unique(cols_p[act_p & (own_p == q)]) if q != p \
                else np.zeros(0, np.int64)
            needed[p][q] = ids
    H = max((ids.size for row in needed for ids in row), default=1)
    H = max(H, 1)
    send_idx = np.zeros((S, S, H), dtype=np.int32)
    # augmented-buffer position of each global id, per receiving shard p
    recv_pos = [dict() for _ in range(S)]
    for p in range(S):
        for q in range(S):
            ids = needed[p][q]
            send_idx[q, p, : ids.size] = lay.local_index(ids)
            base = per + q * H
            for slot, gid in enumerate(ids):
                recv_pos[p][int(gid)] = base + slot
    cols_remap = np.zeros_like(dist.cols)
    for p in range(S):
        cols_p = dist.cols[p]
        own_p = lay.owner_of(cols_p)
        local = lay.local_index(cols_p)
        remap = np.where(own_p == p, local, 0)
        # Zero-value slots keep remap 0: x_local[0] times value 0 is 0.
        rem_mask = (own_p != p) & (dist.data[p] != 0)
        if rem_mask.any():
            flat = cols_p[rem_mask]
            remap_rem = np.array([recv_pos[p][int(g)] for g in flat],
                                 dtype=np.int32)
            remap[rem_mask] = remap_rem
        cols_remap[p] = remap
    return HaloProgram(send_idx=send_idx, cols_remap=cols_remap, halo=H,
                       comm_elems_per_shard=S * H)


def make_halo_spmv_fn(dist, halo: HaloProgram, mesh: Mesh,
                      axis: str = "model", *, use_kernel: bool = False):
    """Deprecated shim over :func:`repro.core.program.make_program_spmv_fn`
    (old ``f(data, cols_remap, send_idx, x_shards)`` signature).

    Collective volume: S*H elements/shard (halo) vs padded_length
    (all-gather) — the ratio is exactly the paper's block-layout locality
    win, measured in ICI bytes.  The executed program uses the plan's own
    halo prologue; a non-halo plan is re-lowered with ``exchange="halo"``
    first so the shim keeps its historical meaning.
    """
    _warn_deprecated("make_halo_spmv_fn",
                     "repro.core.program.make_program_spmv_fn")
    from .program import make_program_spmv_fn
    prog = dist
    if prog.plan.exchange != "halo" or prog.plan.shard_exchanges:
        # Historical behaviour: this factory always produced the uniform
        # halo program for the plan's base, whatever plan.exchange said.
        prog = lower_with_exchange(
            prog, dataclasses.replace(prog.plan, exchange="halo",
                                      shard_exchanges=None))
    inner = make_program_spmv_fn(prog, mesh, axis=axis,
                                 use_kernel=use_kernel)

    @jax.jit
    def fn(data, cols_remap, send_idx, x_shards):
        del data, cols_remap, send_idx
        return inner(x_shards)
    return fn


def lower_with_exchange(program, new_plan: SpmvPlan):
    """Clone a program under a different exchange (same base otherwise).

    The exchange only changes the executor's prologue, not the stages, so
    every stage/accounting object is shared with the source program."""
    return dataclasses.replace(program, plan=new_plan)


def __getattr__(name):
    if name == "DistributedSpmv":       # deprecated alias of the program IR
        from .program import SpmvProgram
        return SpmvProgram
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
