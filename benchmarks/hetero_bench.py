"""Heterogeneous-program benchmarks: per-shard kernel selection vs the
best uniform/non-split alternative, on two workloads.

``--workload mixed`` (default): ``data.matrices.mixed_structure`` — a
dense FEM-style band (regular ~lane-width rows, ELL-friendly) glued to a
short-row scattered sparse block with zipf row lengths (webbase-like,
where the 128-lane ELL/HYB slab floor wastes >90% of its slots and the
nonzero-balanced segmented format wins) — so under a contiguous row
partition the two regimes land on *different shards*.  One global
(kernel) choice must either pay the lane floor on the sparse shards
(ell/hyb) or pay scan/scatter overhead on the regular band (seg); the
per-shard autotuner pays ``sum_p min_k`` instead of ``min_k sum_p``.

``--workload pipeline``: ``data.matrices.halo_spikes`` — broad-reader
rows over a tight local band, the exchange-bound regime.  The headline
is the modeled **device-path** (SPMD) latency of the pre-pipeline serial
schedule vs the pipelined one (:func:`repro.core.plan.device_path_model`
over the full ranking, best-achievable vs best-achievable); the
acceptance gate is >= 1.15x on the full run, recorded via ``perf_probe
--pipeline``.  With enough visible devices the two schedules are also
run through the real shard_map executor and checked bitwise-equal.

``--workload blocked``: ``data.matrices.blocked_band`` — (8, 128)-aligned
dense tiles along a band (1-4 tiles per 8-row block, so ELL pays the
shard-wide max width on every row and seg pays scan bookkeeping on
perfectly regular rows) glued to a short-row scattered block where a
stray nonzero would drag a whole 1024-cell tile in.  The headline is the
kernel-slot term of the best **tile**-using per-shard program vs the best
program whose kernels avoid ``tile`` entirely — the acceptance gate is
>= 1.2x on the full run, recorded via ``perf_probe --tile``.

``--workload powerlaw_tail``: ``data.matrices.powerlaw_tail`` — a
handful of fully-dense *monster rows* over a uniform short-row
background (the paper's §IV-D hot-spot distilled).  A nonzero-balanced
partition hands a shard a couple of monster rows; the seg carry chain
then serializes one carry per chunk of the longest row, and the
split-nnz two-stage ``split`` family is the cure.  The headline is the
kernel-slot term of the best split-using program vs the best *non-split*
program (autotuned over the same grid minus ``split``) — the acceptance
gate is >= 1.1x on the full run.

Reported (and recorded in ``BENCH_emu.json`` via ``perf_probe --hetero``
/ ``perf_probe --split``):

* modeled total cycles of the best baseline candidate vs the best
  per-shard (mixed) / split-using (powerlaw_tail) candidate;
* the kernel-execution-slot term alone (the axis the per-shard choice
  actually moves);
* host wall-clock per served SpMV for both lowered programs through the
  numpy executor backend, for reference;
* an oracle check: both programs reproduce ``csr_matvec``.

Usage::

    PYTHONPATH=src python -m benchmarks.hetero_bench              # full
    PYTHONPATH=src python -m benchmarks.hetero_bench --fast \\
        --budget-seconds 120                                      # CI smoke
    PYTHONPATH=src python -m benchmarks.hetero_bench \\
        --workload powerlaw_tail --fast --budget-seconds 120      # CI split
    PYTHONPATH=src python -m benchmarks.perf_probe --hetero       # + record
    PYTHONPATH=src python -m benchmarks.perf_probe --split        # + record
"""
from __future__ import annotations

import argparse
import json
import time

import numpy as np

from repro.core.partition import make_partition
from repro.core.plan import DEFAULT_PROBE, autotune, device_path_model
from repro.core.program import execute, lower
from repro.core.reorder import reordering_permutation
from repro.core.sparse_matrix import csr_matvec
from repro.data.matrices import blocked_band, halo_spikes, mixed_structure, \
    powerlaw_tail


def _plan_str(p) -> str:
    ex = p.exchange if p.shard_exchanges is None else \
        f"[{'+'.join(p.shard_exchanges)}]"
    s = f"{p.reordering}/{p.layout}/{p.distribution}/{ex}"
    if p.shard_kernels is not None:
        return f"{s}/[{'+'.join(p.shard_kernels)}]"
    return f"{s}/{p.kernel}"


def _host_us_per_spmv(prog, x, repeats: int = 10) -> float:
    """Median-of-repeats wall clock of the serving (numpy) executor."""
    execute(prog, x)                      # warm-up
    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        execute(prog, x)
        times.append(time.perf_counter() - t0)
    return float(np.median(times)) * 1e6


def run_hetero_bench(*, M: int = 4096, nnz_per_row: int = 33,
                     shards: int = 8, probe: int | str | None = None,
                     seed: int = 0, fast: bool = False) -> dict:
    """Run the mixed-structure scenario; returns the headline dict.

    ``probe=None`` defaults to :data:`repro.core.plan.DEFAULT_PROBE`.
    The recorded full run (``perf_probe --hetero``) passes
    ``probe="auto"``: the structure-preserving bases this matrix rewards
    rank poorly on the analytic issue term (the dense band is
    locality-rich but load-imbalanced), so the analytic-vs-measured
    inversion rate stays unstable and adaptive probing keeps spending
    probes until those bases are measured — no fixed full-grid budget
    required.
    """
    probe = DEFAULT_PROBE if probe is None else probe
    if fast:
        M, shards = 1024, 4
    A = mixed_structure(M, M * nnz_per_row, seed=seed)
    choice = autotune(A, num_shards=shards, seed=seed, probe=probe)
    # The ranking is probe-aware (measured bases first), so "best" is the
    # first candidate of each class in ranking order — not min by the
    # analytic total, which would compare across unprobed bases.
    uniform = [r for r in choice.ranking if r.plan.shard_kernels is None]
    hetero = [r for r in choice.ranking if r.plan.shard_kernels is not None]
    best_uni = uniform[0]
    best_het = hetero[0] if hetero else None

    entry = {
        "workload": "hetero/mixed_structure", "M": A.nrows, "nnz": A.nnz,
        "shards": shards, "probe": probe,
        "chosen_plan": _plan_str(choice.plan),
        "chosen_is_per_shard": choice.plan.shard_kernels is not None,
        "best_global_plan": _plan_str(best_uni.plan),
        "per_shard_plan": None if best_het is None else
        _plan_str(best_het.plan),
        "shard_kernels": None if best_het is None else
        list(best_het.plan.shard_kernels),
    }
    if best_het is None:
        entry["model_total_cycles"] = {
            "best_global": round(best_uni.cost.total, 1),
            "per_shard": None, "speedup": 0.0}
        entry["oracle_ok"] = False
        return entry

    entry["model_total_cycles"] = {
        "best_global": round(best_uni.cost.total, 1),
        "per_shard": round(best_het.cost.total, 1),
        "speedup": round(best_uni.cost.total /
                         max(best_het.cost.total, 1e-12), 3)}
    entry["model_kernel_cycles"] = {
        "best_global": round(best_uni.cost.padding_cycles, 1),
        "per_shard": round(best_het.cost.padding_cycles, 1),
        "speedup": round(best_uni.cost.padding_cycles /
                         max(best_het.cost.padding_cycles, 1e-12), 3)}

    prog_uni = lower(A, best_uni.plan)
    prog_het = lower(A, best_het.plan)
    x = np.random.default_rng(seed).standard_normal(A.ncols)
    ref = csr_matvec(A, x)
    entry["oracle_ok"] = bool(
        np.allclose(execute(prog_uni, x), ref, atol=1e-4, rtol=1e-5) and
        np.allclose(execute(prog_het, x), ref, atol=1e-4, rtol=1e-5))
    entry["host_us_per_spmv"] = {
        "best_global": round(_host_us_per_spmv(prog_uni, x), 1),
        "per_shard": round(_host_us_per_spmv(prog_het, x), 1)}
    return entry


def check(entry: dict) -> bool:
    """Acceptance gates CI smoke-tests: the autotuner's winner is a
    genuinely heterogeneous per-shard program, it strictly beats the best
    global (uniform-kernel) plan on the analytic model, and both programs
    reproduce the exact oracle."""
    return (entry.get("shard_kernels") is not None and
            len(set(entry["shard_kernels"])) > 1 and
            entry["chosen_is_per_shard"] and
            entry["model_total_cycles"]["speedup"] > 1.0 and
            entry["oracle_ok"])


def _plan_kernels(plan, shards: int) -> tuple:
    return plan.shard_kernels if plan.shard_kernels is not None \
        else (plan.kernel,) * shards


def run_split_bench(*, M: int = 8192, shards: int = 8, n_monster: int = 8,
                    probe: int | str | None = None, seed: int = 0,
                    fast: bool = False) -> dict:
    """Run the power-law-tail (monster-row) scenario.

    Autotunes the full kernel grid and, on the *same* ranking, compares
    the best split-using candidate against the best candidate whose
    kernels avoid ``split`` entirely, on the kernel-slot term (the axis
    the split family moves; the shared Emu-visible terms cancel).  Full
    scale puts a 16-chunk carry chain on each monster row (M=8192 dense
    rows over 512-element chunks); ``fast`` shrinks to a 4-chunk span —
    still split-selectable, smaller margin.
    """
    probe = DEFAULT_PROBE if probe is None else probe
    if fast:
        M, shards, n_monster = 2048, 4, 4
    A = powerlaw_tail(M, 2 * n_monster * M, n_monster=n_monster, seed=seed)
    choice = autotune(A, num_shards=shards, seed=seed, probe=probe)

    with_split = [r for r in choice.ranking
                  if "split" in _plan_kernels(r.plan, shards)]
    no_split = [r for r in choice.ranking
                if "split" not in _plan_kernels(r.plan, shards)]
    best_split = min(with_split, key=lambda r: r.cost.padding_cycles) \
        if with_split else None
    best_ns = min(no_split, key=lambda r: r.cost.padding_cycles)

    entry = {
        "workload": "split/powerlaw_tail", "M": A.nrows, "nnz": A.nnz,
        "shards": shards, "probe": probe, "n_monster": n_monster,
        "chosen_plan": _plan_str(choice.plan),
        "split_in_winner":
            "split" in _plan_kernels(choice.plan, shards),
        "best_nonsplit_plan": _plan_str(best_ns.plan),
        "split_plan": None if best_split is None else
        _plan_str(best_split.plan),
        "split_kernels": None if best_split is None else
        list(_plan_kernels(best_split.plan, shards)),
    }
    if best_split is None:
        entry["model_kernel_cycles"] = {
            "best_nonsplit": round(best_ns.cost.padding_cycles, 1),
            "split": None, "speedup": 0.0}
        entry["oracle_ok"] = False
        return entry

    entry["model_kernel_cycles"] = {
        "best_nonsplit": round(best_ns.cost.padding_cycles, 1),
        "split": round(best_split.cost.padding_cycles, 1),
        "speedup": round(best_ns.cost.padding_cycles /
                         max(best_split.cost.padding_cycles, 1e-12), 3)}
    entry["model_total_cycles"] = {
        "best_nonsplit": round(best_ns.cost.total, 1),
        "split": round(best_split.cost.total, 1),
        "speedup": round(best_ns.cost.total /
                         max(best_split.cost.total, 1e-12), 3)}

    prog_ns = lower(A, best_ns.plan)
    prog_spl = lower(A, best_split.plan)
    entry["split_counts"] = [
        st.split.num_splits if st.split is not None else 1
        for st in prog_spl.stages]
    x = np.random.default_rng(seed).standard_normal(A.ncols)
    ref = csr_matvec(A, x)
    entry["oracle_ok"] = bool(
        np.allclose(execute(prog_ns, x), ref, atol=1e-4, rtol=1e-5) and
        np.allclose(execute(prog_spl, x), ref, atol=1e-4, rtol=1e-5))
    entry["host_us_per_spmv"] = {
        "best_nonsplit": round(_host_us_per_spmv(prog_ns, x), 1),
        "split": round(_host_us_per_spmv(prog_spl, x), 1)}
    return entry


def check_split(entry: dict, *, fast: bool = False) -> bool:
    """Acceptance gates for the powerlaw_tail workload: the autotuner
    reaches ``split`` on its own, the best split-using program beats the
    best non-split one on the kernel-slot term (>= 1.1x on the recorded
    full run; a strict win suffices at CI-smoke scale, where the carry
    chain is only 4 chunks), and both programs reproduce the oracle."""
    bar = 1.0 if fast else 1.1
    mk = entry.get("model_kernel_cycles", {})
    return (entry.get("split_in_winner", False) and
            mk.get("split") is not None and
            (mk["speedup"] > bar if fast else mk["speedup"] >= bar) and
            entry.get("oracle_ok", False))


def run_tile_bench(*, M: int = 2048, nnz_per_row: int = 215,
                   shards: int = 8, probe: int | str | None = None,
                   seed: int = 0, fast: bool = False) -> dict:
    """Run the blocked-band (bitmask-tiled) scenario.

    Autotunes the full kernel grid and, on the *same* ranking, compares
    the best tile-using candidate against the best candidate whose
    kernels avoid ``tile`` entirely, on the kernel-slot term (the axis
    the tiled format moves; the shared Emu-visible terms cancel).
    ``nnz_per_row`` ~215 makes the dense band span about half the rows
    (the generator sizes the band from the nnz budget: ~2.5 fully dense
    (8, 128) tiles per 8-row block), so under a contiguous partition the
    banded and scattered regimes land on different shards and the winner
    is a mixed tile/scalar program.
    """
    probe = DEFAULT_PROBE if probe is None else probe
    if fast:
        M, shards = 512, 4
    A = blocked_band(M, M * nnz_per_row, seed=seed)
    choice = autotune(A, num_shards=shards, seed=seed, probe=probe)

    with_tile = [r for r in choice.ranking
                 if "tile" in _plan_kernels(r.plan, shards)]
    no_tile = [r for r in choice.ranking
               if "tile" not in _plan_kernels(r.plan, shards)]
    best_tile = min(with_tile, key=lambda r: r.cost.padding_cycles) \
        if with_tile else None
    best_nt = min(no_tile, key=lambda r: r.cost.padding_cycles)

    entry = {
        "workload": "tile/blocked_band", "M": A.nrows, "nnz": A.nnz,
        "shards": shards, "probe": probe,
        "chosen_plan": _plan_str(choice.plan),
        "tile_in_winner": "tile" in _plan_kernels(choice.plan, shards),
        "best_nontile_plan": _plan_str(best_nt.plan),
        "tile_plan": None if best_tile is None else _plan_str(best_tile.plan),
        "tile_kernels": None if best_tile is None else
        list(_plan_kernels(best_tile.plan, shards)),
    }
    if best_tile is None:
        entry["model_kernel_cycles"] = {
            "best_nontile": round(best_nt.cost.padding_cycles, 1),
            "tile": None, "speedup": 0.0}
        entry["oracle_ok"] = False
        return entry

    entry["model_kernel_cycles"] = {
        "best_nontile": round(best_nt.cost.padding_cycles, 1),
        "tile": round(best_tile.cost.padding_cycles, 1),
        "speedup": round(best_nt.cost.padding_cycles /
                         max(best_tile.cost.padding_cycles, 1e-12), 3)}
    entry["model_total_cycles"] = {
        "best_nontile": round(best_nt.cost.total, 1),
        "tile": round(best_tile.cost.total, 1),
        "speedup": round(best_nt.cost.total /
                         max(best_tile.cost.total, 1e-12), 3)}

    prog_nt = lower(A, best_nt.plan)
    prog_tile = lower(A, best_tile.plan)
    entry["tile_counts"] = [
        st.tile.num_tiles if st.tile is not None else 0
        for st in prog_tile.stages]
    x = np.random.default_rng(seed).standard_normal(A.ncols)
    ref = csr_matvec(A, x)
    entry["oracle_ok"] = bool(
        np.allclose(execute(prog_nt, x), ref, atol=1e-4, rtol=1e-5) and
        np.allclose(execute(prog_tile, x), ref, atol=1e-4, rtol=1e-5))
    entry["host_us_per_spmv"] = {
        "best_nontile": round(_host_us_per_spmv(prog_nt, x), 1),
        "tile": round(_host_us_per_spmv(prog_tile, x), 1)}
    return entry


def check_tile(entry: dict, *, fast: bool = False) -> bool:
    """Acceptance gates for the blocked workload: the autotuner's own
    grid reaches ``tile`` (the tile candidate is ranked, not forced),
    the best tile-using program beats the best tile-free one on the
    kernel-slot term (>= 1.2x on the recorded full run; a strict win
    suffices at CI-smoke scale), and both programs reproduce the
    oracle.  The *overall* winner is not required to use tile: the
    Emu-probed ranking may prefer a random-reordering base — which
    destroys the block structure tile feeds on — for migration-balance
    reasons the kernel-slot axis cannot see."""
    bar = 1.0 if fast else 1.2
    mk = entry.get("model_kernel_cycles", {})
    return (entry.get("tile_kernels") is not None and
            "tile" in entry["tile_kernels"] and
            mk.get("tile") is not None and
            (mk["speedup"] > bar if fast else mk["speedup"] >= bar) and
            entry.get("oracle_ok", False))


def run_pipeline_bench(*, M: int = 8192, nnz_per_row: int = 8,
                       shards: int = 8, seed: int = 0,
                       fast: bool = False) -> dict:
    """Run the exchange-bound pipelining scenario on ``halo_spikes``.

    The headline is the modeled **device-path** (SPMD shard_map) latency:
    serial schedule (exchange completes before any kernel work, the
    pre-pipeline executor) vs the pipelined schedule (all-local rows run
    while the collective is in flight) — :func:`device_path_model` over
    the full autotune ranking, best-achievable vs best-achievable, so a
    plan change cannot manufacture the win.  ``halo_spikes`` puts a few
    broad-reader rows on every shard over a tight local band: each
    shard's unique remote-column set is large (the exchange term rivals
    the kernel term) while most rows stay local (there is work to hide
    the exchange behind).

    When enough devices are visible (``XLA_FLAGS
    --xla_force_host_platform_device_count``), the pipelined and serial
    schedules are additionally executed through the real shard_map path
    and checked bitwise-equal, with wall-clock recorded for reference.
    """
    if fast:
        M, shards = 2048, 4
    A0 = halo_spikes(M, M * nnz_per_row, seed=seed)
    choice = autotune(A0, num_shards=shards, seed=seed, probe=0)

    cache: dict = {}
    best_ser = best_pipe = None
    for r in choice.ranking:
        plan = r.plan
        bk = (plan.reordering, plan.distribution)
        if bk not in cache:
            perm = reordering_permutation(A0, plan.reordering,
                                          seed=plan.seed, parts=shards)
            Ar = A0 if plan.reordering == "none" else A0.permuted(perm, perm)
            cache[bk] = (Ar, make_partition(Ar, shards, plan.distribution))
        Ar, part = cache[bk]
        m = device_path_model(Ar, part, plan)
        if best_ser is None or m["serial_cycles"] < best_ser[0]:
            best_ser = (m["serial_cycles"], plan)
        if best_pipe is None or m["pipelined_cycles"] < best_pipe[0]:
            best_pipe = (m["pipelined_cycles"], plan, m)

    ser_cycles, ser_plan = best_ser
    pipe_cycles, pipe_plan, pipe_terms = best_pipe
    entry = {
        "workload": "pipeline/halo_spikes", "M": A0.nrows, "nnz": A0.nnz,
        "shards": shards,
        "serial_plan": _plan_str(ser_plan),
        "pipelined_plan": _plan_str(pipe_plan),
        "shard_exchanges": list(pipe_plan.resolved_shard_exchanges()),
        "model_device_cycles": {
            "serial": round(ser_cycles, 1),
            "pipelined": round(pipe_cycles, 1),
            "speedup": round(ser_cycles / max(pipe_cycles, 1e-12), 3)},
        "pipelined_terms": {k: round(v, 1) for k, v in pipe_terms.items()
                            if k != "speedup"},
    }

    prog = lower(A0, pipe_plan)
    x = np.random.default_rng(seed).standard_normal(A0.ncols)
    ref = csr_matvec(A0, x)
    entry["oracle_ok"] = bool(np.allclose(execute(prog, x), ref,
                                          atol=1e-4, rtol=1e-5))

    import jax
    from jax.sharding import AxisType
    n_dev = jax.device_count()
    entry["device_count"] = n_dev
    if n_dev >= shards:
        mesh = jax.make_mesh((shards,), ("model",),
                             axis_types=(AxisType.Auto,),
                             devices=jax.devices()[:shards])
        y_pipe = execute(prog, x, backend="shard_map", mesh=mesh)
        y_ser = execute(prog, x, backend="shard_map", mesh=mesh,
                        pipeline=False)
        entry["device_bitwise_ok"] = bool(
            np.array_equal(np.asarray(y_pipe), np.asarray(y_ser)))
        entry["device_oracle_ok"] = bool(
            np.allclose(np.asarray(y_pipe), ref, atol=2e-4, rtol=1e-4))
        from repro.core.program import make_program_spmv_fn, scatter_x
        xs = scatter_x(prog, x)
        for key, flag in (("pipelined", True), ("serial", False)):
            fn = make_program_spmv_fn(prog, mesh, pipeline=flag)
            jax.block_until_ready(fn(xs))       # compile outside the clock
            fn_t = []
            for _ in range(5):
                t0 = time.perf_counter()
                jax.block_until_ready(fn(xs))
                fn_t.append(time.perf_counter() - t0)
            entry.setdefault("device_host_us_per_spmv", {})[key] = \
                round(float(np.median(fn_t)) * 1e6, 1)
    return entry


def check_pipeline(entry: dict, *, fast: bool = False) -> bool:
    """Acceptance gates for the pipeline workload: the best-achievable
    pipelined device-path latency beats the best-achievable serial one by
    >= 1.15x on the recorded full run (a strict win suffices at CI-smoke
    scale), the pipelined plan's program reproduces the oracle, and —
    whenever enough devices were visible for the real shard_map phase to
    run — its two schedules are bitwise-equal and match the oracle."""
    bar = 1.0 if fast else 1.15
    sp = entry.get("model_device_cycles", {}).get("speedup", 0.0)
    device_ran = entry.get("device_count", 0) >= entry["shards"]
    return ((sp > bar if fast else sp >= bar) and
            entry.get("oracle_ok", False) and
            (not device_ran or (entry.get("device_bitwise_ok", False) and
                                entry.get("device_oracle_ok", False))))


def _probe_arg(s: str):
    """CLI probe budget: an int, or the literal string ``auto``."""
    if s == "auto":
        return s
    return int(s)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload",
                    choices=("mixed", "powerlaw_tail", "pipeline",
                             "blocked"),
                    default="mixed",
                    help="mixed: per-shard vs best-global on "
                         "mixed_structure; powerlaw_tail: split vs best "
                         "non-split on monster rows; pipeline: serial vs "
                         "pipelined device schedule on halo_spikes; "
                         "blocked: tile vs best non-tile on blocked_band")
    ap.add_argument("--m", type=int, default=None, help="matrix dimension "
                    "(default: per-workload)")
    ap.add_argument("--nnz-per-row", type=int, default=33,
                    help="mixed workload only")
    ap.add_argument("--shards", type=int, default=8)
    ap.add_argument("--probe", type=_probe_arg, default=None,
                    help="autotune probe budget: an int, or 'auto' for "
                         "adaptive probing (probe until the "
                         "measured-vs-analytic inversion rate stabilizes; "
                         "default: repro.core.plan.DEFAULT_PROBE)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--fast", action="store_true",
                    help="CI smoke: smaller matrix, analytic-only ranking, "
                         "same gates")
    ap.add_argument("--budget-seconds", type=float, default=None,
                    help="fail if the whole run exceeds this wall-clock "
                         "budget (CI tripwire)")
    ap.add_argument("--json", action="store_true",
                    help="print the entry as JSON only")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    t0 = time.perf_counter()
    if args.workload == "pipeline":
        kwargs = {} if args.m is None else {"M": args.m}
        entry = run_pipeline_bench(shards=args.shards, seed=args.seed,
                                   fast=args.fast, **kwargs)
        ok = check_pipeline(entry, fast=args.fast)
    elif args.workload == "powerlaw_tail":
        kwargs = {} if args.m is None else {"M": args.m}
        entry = run_split_bench(shards=args.shards, probe=args.probe,
                                seed=args.seed, fast=args.fast, **kwargs)
        ok = check_split(entry, fast=args.fast)
    elif args.workload == "blocked":
        kwargs = {} if args.m is None else {"M": args.m}
        entry = run_tile_bench(shards=args.shards, probe=args.probe,
                               seed=args.seed, fast=args.fast, **kwargs)
        ok = check_tile(entry, fast=args.fast)
    else:
        entry = run_hetero_bench(M=args.m if args.m is not None else 4096,
                                 nnz_per_row=args.nnz_per_row,
                                 shards=args.shards, probe=args.probe,
                                 seed=args.seed, fast=args.fast)
        ok = check(entry)
    wall = time.perf_counter() - t0
    entry["wall_seconds"] = round(wall, 2)
    if args.budget_seconds is not None and wall > args.budget_seconds:
        ok = False
        entry["budget_exceeded"] = True

    if args.json:
        print(json.dumps(entry, indent=2))
    elif args.workload == "pipeline":
        print(f"hetero bench: {entry['workload']} M={entry['M']} "
              f"nnz={entry['nnz']} shards={entry['shards']}")
        print(f"  serial plan : {entry['serial_plan']}")
        print(f"  pipelined   : {entry['pipelined_plan']} "
              f"(exchanges {entry['shard_exchanges']})")
        md = entry["model_device_cycles"]
        bar = "> 1.0 (fast)" if args.fast else ">= 1.15"
        print(f"  device path : {md['serial']} -> {md['pipelined']} "
              f"cycles ({md['speedup']}x, bar {bar})")
        t = entry["pipelined_terms"]
        print(f"  terms       : kernel {t['kernel_cycles']} = local "
              f"{t['local_slice_cycles']} || comm {t['comm_cycles']} "
              f"then remote {t['remote_slice_cycles']}")
        if "device_bitwise_ok" in entry:
            h = entry.get("device_host_us_per_spmv", {})
            print(f"  shard_map   : bitwise_ok={entry['device_bitwise_ok']} "
                  f"oracle_ok={entry['device_oracle_ok']} host "
                  f"{h.get('serial')} -> {h.get('pipelined')} us/SpMV "
                  f"(reference only)")
        budget = f", wall {wall:.1f}s <= {args.budget_seconds:.0f}s" \
            if args.budget_seconds is not None else f", wall {wall:.1f}s"
        print(f"  -> {'PASS' if ok else 'FAIL'} "
              f"(oracle_ok={entry['oracle_ok']}{budget})")
    elif args.workload == "blocked":
        print(f"hetero bench: {entry['workload']} M={entry['M']} "
              f"nnz={entry['nnz']} shards={entry['shards']}")
        print(f"  chosen      : {entry['chosen_plan']} "
              f"(tile_in_winner={entry['tile_in_winner']})")
        print(f"  non-tile    : {entry['best_nontile_plan']}")
        print(f"  tile        : {entry['tile_plan']}")
        mk = entry["model_kernel_cycles"]
        bar = "> 1.0 (fast)" if args.fast else ">= 1.2"
        print(f"  kernel term : {mk['best_nontile']} -> {mk['tile']} "
              f"cycles ({mk['speedup']}x, bar {bar})")
        if "model_total_cycles" in entry:
            mt = entry["model_total_cycles"]
            print(f"  model total : {mt['best_nontile']} -> {mt['tile']} "
                  f"cycles ({mt['speedup']}x)")
        if "tile_counts" in entry:
            print(f"  tile counts : {entry['tile_counts']} "
                  f"(kernels {entry['tile_kernels']})")
        if "host_us_per_spmv" in entry:
            h = entry["host_us_per_spmv"]
            print(f"  host        : {h['best_nontile']} -> {h['tile']} "
                  f"us/SpMV (numpy executor; reference only)")
        budget = f", wall {wall:.1f}s <= {args.budget_seconds:.0f}s" \
            if args.budget_seconds is not None else f", wall {wall:.1f}s"
        print(f"  -> {'PASS' if ok else 'FAIL'} "
              f"(oracle_ok={entry['oracle_ok']}{budget})")
    elif args.workload == "powerlaw_tail":
        print(f"hetero bench: {entry['workload']} M={entry['M']} "
              f"nnz={entry['nnz']} shards={entry['shards']}")
        print(f"  chosen      : {entry['chosen_plan']} "
              f"(split_in_winner={entry['split_in_winner']})")
        print(f"  non-split   : {entry['best_nonsplit_plan']}")
        print(f"  split       : {entry['split_plan']}")
        mk = entry["model_kernel_cycles"]
        bar = "> 1.0 (fast)" if args.fast else ">= 1.1"
        print(f"  kernel term : {mk['best_nonsplit']} -> {mk['split']} "
              f"cycles ({mk['speedup']}x, bar {bar})")
        if "model_total_cycles" in entry:
            mt = entry["model_total_cycles"]
            print(f"  model total : {mt['best_nonsplit']} -> {mt['split']} "
                  f"cycles ({mt['speedup']}x)")
        if "split_counts" in entry:
            print(f"  split counts: {entry['split_counts']} "
                  f"(kernels {entry['split_kernels']})")
        if "host_us_per_spmv" in entry:
            h = entry["host_us_per_spmv"]
            print(f"  host        : {h['best_nonsplit']} -> {h['split']} "
                  f"us/SpMV (numpy executor; reference only)")
        budget = f", wall {wall:.1f}s <= {args.budget_seconds:.0f}s" \
            if args.budget_seconds is not None else f", wall {wall:.1f}s"
        print(f"  -> {'PASS' if ok else 'FAIL'} "
              f"(oracle_ok={entry['oracle_ok']}{budget})")
    else:
        print(f"hetero bench: {entry['workload']} M={entry['M']} "
              f"nnz={entry['nnz']} shards={entry['shards']}")
        print(f"  best global : {entry['best_global_plan']}")
        print(f"  per-shard   : {entry['per_shard_plan']}")
        mt = entry["model_total_cycles"]
        print(f"  model total : {mt['best_global']} -> {mt['per_shard']} "
              f"cycles ({mt['speedup']}x, bar > 1.0)")
        if "model_kernel_cycles" in entry:
            mk = entry["model_kernel_cycles"]
            print(f"  kernel term : {mk['best_global']} -> "
                  f"{mk['per_shard']} cycles ({mk['speedup']}x)")
        if "host_us_per_spmv" in entry:
            h = entry["host_us_per_spmv"]
            print(f"  host        : {h['best_global']} -> {h['per_shard']} "
                  f"us/SpMV (numpy executor; reference only)")
        budget = f", wall {wall:.1f}s <= {args.budget_seconds:.0f}s" \
            if args.budget_seconds is not None else f", wall {wall:.1f}s"
        print(f"  -> {'PASS' if ok else 'FAIL'} "
              f"(oracle_ok={entry['oracle_ok']}{budget})")
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
