#!/usr/bin/env python3
"""Chip smoke test: the served SpMV path end to end on a TPU.

    python3 chip_smoke.py              # one chip
    python3 chip_smoke.py --chips 4    # four chips: the sharded path only

One chip: a ``SparseMatrixEngine`` on a 1-device mesh ingests cop20k_A and
audikw_1 at full Table-I size and answers 1-D and (N, 8) requests; then the
device executor runs each Pallas kernel family (ell, seg, hyb, split,
tile) compiled, on a matrix that suits it.  Four chips: an engine on a
4-device mesh serves cop20k_A autotuned to 4 shards, and a
``mixed_structure`` program with mixed per-shard kernels and exchanges
runs across the chips; the pipelined schedule must equal the serial one
bit for bit.

Every answer is checked against the float64 ``csr_matvec`` oracle:
``|y - y_ref|_i <= 1e-4 * (|A| |x|)_i`` for every row i.  Earlier lines
print, per tenant and per family, the plan, the device operand bytes and
the seconds taken (information, not metrics).  The last line is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
Without a TPU the script exits non-zero with a one-line reason and
prints no result; any failed phase also exits non-zero.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

TOL = 1e-4          # per-row error bound, relative to (|A| |x|)_i
SEED = 0
BATCH = 8           # width of the multi-RHS request
N_VECTORS = 3       # 1-D requests per tenant


def die(msg: str) -> None:
    print(f"chip_smoke: {msg}", file=sys.stderr)
    sys.exit(1)


def info(**kw) -> None:
    print(json.dumps(kw, default=str), flush=True)


def check(A, x, y, what: str) -> float:
    """Max over rows of |y - y_ref| / (|A| |x|); dies above ``TOL``."""
    import numpy as np
    from repro.core.sparse_matrix import csr_matvec

    absA = dataclasses.replace(A, values=np.abs(A.values))
    cols = [slice(None)] if x.ndim == 1 else \
        [(slice(None), b) for b in range(x.shape[1])]
    worst = 0.0
    y = np.asarray(y, dtype=np.float64)
    for c in cols:                        # 1-D oracle calls: numpy's fast path
        ref = csr_matvec(A, x[c])
        bound = csr_matvec(absA, np.abs(x[c]))
        got = y[c]
        if got.shape != ref.shape or not np.isfinite(got).all():
            die(f"{what}: shape {got.shape} vs {ref.shape} or non-finite")
        err = np.abs(got - ref)
        if (err > TOL * bound).any():
            i = int(np.argmax(err - TOL * bound))
            die(f"{what}: row {i} error {err[i]:.3e} > {TOL} * "
                f"{bound[i]:.3e}")
        worst = max(worst, float(np.max(err / np.where(bound > 0, bound, 1))))
    return worst


def on_device(fn) -> list:
    """Platforms holding the device function's operands."""
    import jax
    return sorted({d.platform for a in jax.tree.leaves(fn.operands)
                   for d in a.devices()})


def warm_call_s(fn, xs) -> float:
    """Host-clock seconds of one device call that is already compiled:
    the transfer of x, the step and the wait for its result."""
    import jax
    t0 = time.perf_counter()
    jax.block_until_ready(fn(xs))
    return time.perf_counter() - t0


def serve_tenant(eng, name: str, A, plan=None) -> None:
    import numpy as np
    from repro.core.program import scatter_x
    t0 = time.perf_counter()
    choice = eng.ingest(name, A, plan)
    ingest_s = time.perf_counter() - t0
    fn = eng.device_fn(name)
    rng = np.random.default_rng(SEED)
    errs, first_s = [], None
    for i in range(N_VECTORS):
        x = rng.standard_normal(A.ncols)
        t0 = time.perf_counter()
        y = eng.spmv(name, x)
        if i == 0:
            first_s = time.perf_counter() - t0
        errs.append(check(A, x, y, f"{name} 1-D request {i}"))
    X = rng.standard_normal((A.ncols, BATCH))
    t0 = time.perf_counter()
    Y = eng.spmv(name, X)
    first_b_s = time.perf_counter() - t0
    errs.append(check(A, X, Y, f"{name} ({A.ncols}, {BATCH}) request"))
    # The batched step loops over its vectors on the device: compare the
    # warm (N, B) call with the warm 1-D one.
    warm_s = warm_call_s(fn, scatter_x(fn.program, x))
    warm_b_s = warm_call_s(fn, scatter_x(fn.program, X))
    where = on_device(fn)
    if where != ["tpu"]:
        die(f"{name}: operands live on {where}, not on the TPU")
    info(phase="tenant", name=name, rows=A.nrows, nnz=A.nnz,
         plan=dataclasses.asdict(choice.plan), autotuned=plan is None,
         operand_bytes=fn.operand_bytes, operands_on=where,
         ingest_s=ingest_s, first_call_s=first_s,
         first_batched_call_s=first_b_s, warm_call_s=warm_s,
         warm_batched_call_s=warm_b_s, max_norm_err=max(errs))


def kernel_family(mesh, family: str, A) -> None:
    import numpy as np
    from repro.core.program import gather_b, lower, make_program_spmv_fn, \
        scatter_x
    from repro.core.spmv import SpmvPlan

    t0 = time.perf_counter()
    prog = lower(A, SpmvPlan(kernel=family, num_shards=1))
    fn = make_program_spmv_fn(prog, mesh, use_kernel=True)
    build_s = time.perf_counter() - t0
    x = np.random.default_rng(SEED).standard_normal(A.ncols)
    t0 = time.perf_counter()
    y = gather_b(prog, fn(scatter_x(prog, x)))
    first_s = time.perf_counter() - t0
    err = check(A, x, y, f"{family} kernel")
    info(phase="kernel", family=family, rows=A.nrows, nnz=A.nnz,
         shard_kernels=list(prog.shard_kernels()),
         operand_bytes=fn.operand_bytes, lower_and_place_s=build_s,
         first_call_s=first_s, max_norm_err=err)


def one_chip(devices) -> None:
    import jax
    from jax.sharding import AxisType
    from repro.core.spmv import SpmvPlan
    from repro.data.matrices import blocked_band, make_matrix, powerlaw_tail
    from repro.serve.router import MESH_AXIS, SparseMatrixEngine

    mesh = jax.make_mesh((1,), (MESH_AXIS,), axis_types=(AxisType.Auto,),
                         devices=devices[:1])
    eng = SparseMatrixEngine(mesh=mesh)
    t0 = time.perf_counter()
    cop = make_matrix("cop20k_A", scale=1.0, seed=SEED)
    info(phase="generate", name="cop20k_A", s=time.perf_counter() - t0)
    serve_tenant(eng, "cop20k_A", cop)
    t0 = time.perf_counter()
    audi = make_matrix("audikw_1", scale=1.0, seed=SEED)
    info(phase="generate", name="audikw_1", s=time.perf_counter() - t0)
    # At audikw_1's 80M non-zeros the Emu-probed autotune spends about
    # eight minutes of host time, so this tenant comes with the plan that
    # autotune picks for one shard: row-split HYB (p95-capped ELL + COO).
    serve_tenant(eng, "audikw_1", audi,
                 SpmvPlan(kernel="hyb", distribution="row", num_shards=1))
    del eng, audi

    for family in ("ell", "seg", "hyb"):
        kernel_family(mesh, family, cop)
    M = 1 << 17
    kernel_family(mesh, "split",
                  powerlaw_tail(M, 16 * M, n_monster=8, seed=SEED))
    M = 1 << 16
    kernel_family(mesh, "tile",
                  blocked_band(M, 215 * M, band_frac=1.0, seed=SEED))


def four_chips(devices) -> None:
    import jax
    import numpy as np
    from jax.sharding import AxisType
    from repro.core.program import gather_b, lower, make_program_spmv_fn, \
        scatter_x
    from repro.core.spmv import SpmvPlan
    from repro.data.matrices import make_matrix, mixed_structure
    from repro.serve.router import MESH_AXIS, SparseMatrixEngine

    mesh = jax.make_mesh((4,), (MESH_AXIS,), axis_types=(AxisType.Auto,),
                         devices=devices[:4])
    eng = SparseMatrixEngine(mesh=mesh)
    cop = make_matrix("cop20k_A", scale=1.0, seed=SEED)
    serve_tenant(eng, "cop20k_A", cop)

    M = 1 << 17
    mixed = mixed_structure(M, 33 * M, seed=SEED)
    cases = (("cop20k_A", cop, eng.device_fn("cop20k_A").program),
             ("mixed_structure", mixed, lower(mixed, SpmvPlan(
                 num_shards=4, exchange="halo",
                 shard_kernels=("ell", "seg", "hyb", "split"),
                 shard_exchanges=("halo", "allgather", "halo",
                                  "allgather")))))
    rng = np.random.default_rng(SEED)
    for name, A, prog in cases:
        pipe = make_program_spmv_fn(prog, mesh)
        serial = make_program_spmv_fn(prog, mesh, pipeline=False)
        errs = []
        for x in (rng.standard_normal(A.ncols),
                  rng.standard_normal((A.ncols, BATCH))):
            xs = scatter_x(prog, x)
            y_pipe, y_ser = np.asarray(pipe(xs)), np.asarray(serial(xs))
            if not np.array_equal(y_pipe, y_ser):
                die(f"{name} x{x.shape}: pipelined != serial (bitwise)")
            errs.append(check(A, x, gather_b(prog, y_pipe),
                              f"{name} x{x.shape} across 4 chips"))
        info(phase="four_chips", name=name, rows=A.nrows, nnz=A.nnz,
             shard_kernels=list(prog.shard_kernels()),
             shard_exchanges=list(prog.plan.resolved_shard_exchanges()),
             operand_bytes=pipe.operand_bytes, pipelined_equals_serial=True,
             max_norm_err=max(errs))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded path on a 4-chip mesh")
    args = ap.parse_args()

    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    try:
        from repro.launch.compile_cache import enable_compile_cache
    except ImportError as err:
        die(f"the repository's sources are not next to this script ({err})")
    cache = enable_compile_cache()
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        die(f"no TPU: JAX found {devices[0].platform} devices only")
    if len(devices) < args.chips:
        die(f"--chips {args.chips} needs {args.chips} TPUs, found "
            f"{len(devices)}")
    info(phase="start", device_kind=devices[0].device_kind,
         device_count=len(devices), compile_cache=cache)
    t0 = time.perf_counter()
    if args.chips == 4:
        four_chips(devices)
    else:
        one_chip(devices)
    info(phase="done", s=time.perf_counter() - t0)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
