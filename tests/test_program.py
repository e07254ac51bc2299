"""SpmvProgram IR tests: lowering, per-shard stages, and executor
equivalence — the numpy oracle, the one shard_map device program (jnp
oracle *and* Pallas-interpret kernels), and the Emu probe all consume the
same lowered program.  The multi-device backend runs in a subprocess so
the fake devices never leak into this session.
"""
import json
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.core.program import execute, lower, probe_program, relower
from repro.core.sparse_matrix import csr_matvec
from repro.core.spmv import SpmvPlan
from repro.data.matrices import make_matrix, mixed_structure, powerlaw, \
    powerlaw_tail

KERNEL_CONFIGS = [
    ("ell", None),
    ("seg", None),
    ("hyb", None),
    ("split", None),
    ("tile", None),
    ("seg", ("ell", "seg", "hyb", "seg")),      # heterogeneous program
    ("seg", ("ell", "split", "hyb", "seg")),    # heterogeneous with split
    ("seg", ("tile", "split", "tile", "ell")),  # heterogeneous tile/split
]


@pytest.mark.parametrize("layout", ["block", "cyclic"])
@pytest.mark.parametrize("distribution", ["row", "nonzero"])
@pytest.mark.parametrize("kernel,shard_kernels", KERNEL_CONFIGS)
def test_numpy_backend_matches_oracle_on_grid(layout, distribution, kernel,
                                              shard_kernels):
    A = make_matrix("cop20k_A", scale=0.003)
    plan = SpmvPlan(layout=layout, distribution=distribution, kernel=kernel,
                    shard_kernels=shard_kernels, num_shards=4)
    prog = lower(A, plan)
    assert prog.shard_kernels() == plan.resolved_shard_kernels()
    x = np.random.default_rng(0).standard_normal(A.ncols)
    np.testing.assert_allclose(execute(prog, x), csr_matvec(A, x),
                               atol=1e-5, rtol=1e-6)


def test_numpy_backend_batched_bitwise_per_column():
    A = make_matrix("cop20k_A", scale=0.003)
    X = np.random.default_rng(1).standard_normal((A.ncols, 4))
    for kernel, sk in KERNEL_CONFIGS:
        prog = lower(A, SpmvPlan(kernel=kernel, shard_kernels=sk,
                                 num_shards=4, reordering="bfs"))
        Y = execute(prog, X)
        assert Y.shape == (A.nrows, 4)
        for b in range(4):
            assert np.array_equal(Y[:, b], execute(prog, X[:, b])), \
                (kernel, sk, b)
        np.testing.assert_allclose(Y, csr_matvec(A, X), atol=1e-5,
                                   rtol=1e-6)


def test_hyb_stage_really_overflows_and_matches():
    """The capped slab must actually spill on a skewed matrix (otherwise
    HYB degenerates to ELL and the test proves nothing)."""
    A = powerlaw(1024, 40_000, seed=2)
    prog = lower(A, SpmvPlan(kernel="hyb", distribution="row", num_shards=4))
    ovf = sum(st.ell.overflow_vals.size for st in prog.stages)
    assert ovf > 0
    x = np.random.default_rng(3).standard_normal(A.ncols)
    np.testing.assert_allclose(execute(prog, x), csr_matvec(A, x),
                               atol=1e-4, rtol=1e-5)


def test_relower_shares_unchanged_stages():
    A = mixed_structure(1024, 120_000, seed=0)
    p1 = SpmvPlan(num_shards=4, shard_kernels=("ell", "seg", "hyb", "seg"))
    prog = lower(A, p1)
    p2 = SpmvPlan(num_shards=4, shard_kernels=("ell", "ell", "hyb", "seg"))
    prog2 = relower(prog, p2)
    assert prog2.stages[0] is prog.stages[0]
    assert prog2.stages[2] is prog.stages[2]
    assert prog2.stages[3] is prog.stages[3]
    assert prog2.stages[1] is not prog.stages[1]
    assert prog2.stages[1].kernel == "ell"
    x = np.random.default_rng(4).standard_normal(A.ncols)
    np.testing.assert_allclose(execute(prog2, x), csr_matvec(A, x),
                               atol=1e-5, rtol=1e-6)
    # structural objects are shared, not copied
    assert prog2.matrix is prog.matrix and prog2.partition is prog.partition
    with pytest.raises(ValueError, match="base field"):
        relower(prog, SpmvPlan(num_shards=4, layout="cyclic",
                               shard_kernels=("ell", "ell", "hyb", "seg")))


def test_relower_shares_stages_on_unchanged_split_count():
    """Re-planning that keeps a shard's *effective* split count must share
    the stage object; changing the count rebuilds only that stage."""
    A = powerlaw_tail(2048, 2 * 4 * 2048, n_monster=4, seed=0)
    p1 = SpmvPlan(num_shards=4, shard_kernels=("split", "seg", "seg", "seg"),
                  split_counts=(4, 1, 1, 1))
    prog = lower(A, p1)
    assert prog.stages[0].split is not None
    assert prog.stages[0].split.num_splits == 4
    # same requested count -> all stages shared
    prog2 = relower(prog, SpmvPlan(
        num_shards=4, shard_kernels=("split", "seg", "seg", "seg"),
        split_counts=(4, 1, 1, 1)))
    assert all(prog2.stages[p] is prog.stages[p] for p in range(4))
    # different effective count -> only the split stage rebuilds
    prog3 = relower(prog, SpmvPlan(
        num_shards=4, shard_kernels=("split", "seg", "seg", "seg"),
        split_counts=(2, 1, 1, 1)))
    assert prog3.stages[0] is not prog.stages[0]
    assert prog3.stages[0].split.num_splits == 2
    assert all(prog3.stages[p] is prog.stages[p] for p in (1, 2, 3))
    x = np.random.default_rng(5).standard_normal(A.ncols)
    for pr in (prog, prog2, prog3):
        np.testing.assert_allclose(execute(pr, x), csr_matvec(A, x),
                                   atol=1e-4, rtol=1e-5)


def test_degenerate_matrix_empty_shards_all_families():
    """A 6x6 matrix lowered over 4 shards leaves shards with zero rows
    and/or zero nnz; every kernel family must produce a valid no-op stage
    and the exact result (empty-shard lowering regression)."""
    from repro.core.sparse_matrix import csr_from_coo
    A = csr_from_coo([0, 0, 5], [1, 4, 0], [2.0, -1.0, 3.0], (6, 6))
    x = np.arange(6, dtype=np.float64)
    for kernel in ("ell", "seg", "hyb", "split", "tile"):
        for dist in ("row", "nonzero"):
            prog = lower(A, SpmvPlan(kernel=kernel, distribution=dist,
                                     num_shards=4))
            nnz_per_shard = [
                int(A.row_ptr[prog.partition.starts[p + 1]] -
                    A.row_ptr[prog.partition.starts[p]])
                for p in range(4)]
            assert 0 in nnz_per_shard, (kernel, dist)   # genuinely empty
            np.testing.assert_allclose(execute(prog, x), csr_matvec(A, x),
                                       atol=1e-6, err_msg=f"{kernel}/{dist}")
            res = probe_program(prog)               # emu backend runs too
            assert res.ticks > 0


def test_monster_row_numpy_and_emu_backends():
    """Monster-row shard (rows spanning many chunks) through the numpy
    executor and the Emu probe, for seg and split programs."""
    A = powerlaw_tail(2048, 2 * 4 * 2048, n_monster=4, seed=3)
    x = np.random.default_rng(3).standard_normal(A.ncols)
    for sk in (None, ("split", "split", "seg", "seg")):
        plan = SpmvPlan(kernel="seg", shard_kernels=sk,
                        distribution="nonzero", num_shards=4)
        prog = lower(A, plan)
        np.testing.assert_allclose(execute(prog, x), csr_matvec(A, x),
                                   atol=1e-4, rtol=1e-5)
        assert probe_program(prog).ticks > 0


def test_emu_backend_is_deterministic_and_plan_driven():
    A = make_matrix("cop20k_A", scale=0.003)
    prog = lower(A, SpmvPlan(num_shards=4, kernel="seg"))
    r1 = execute(prog, backend="emu")
    r2 = probe_program(prog)
    assert r1.ticks == r2.ticks and r1.migrations == r2.migrations
    # a worse layout really probes slower (cyclic on the banded-ish matrix)
    slow = lower(A, SpmvPlan(num_shards=4, layout="cyclic", kernel="seg"))
    assert probe_program(slow).seconds != r1.seconds


def test_execute_rejects_unknown_backend_and_missing_x():
    A = make_matrix("ford1", scale=0.05)
    prog = lower(A, SpmvPlan(num_shards=4))
    with pytest.raises(ValueError, match="backend"):
        execute(prog, np.zeros(A.ncols), backend="tpu")
    with pytest.raises(ValueError, match="needs an input"):
        execute(prog, backend="numpy")
    with pytest.raises(ValueError, match="mesh"):
        execute(prog, np.zeros(A.ncols), backend="shard_map")


def test_legacy_stacked_views_still_available():
    """Old callers (build_halo, spmv_exchange) read stacked .data/.cols —
    they must exist for any program, and seg_* for uniform-seg ones."""
    A = make_matrix("ford1", scale=0.05)
    het = lower(A, SpmvPlan(num_shards=4,
                            shard_kernels=("ell", "seg", "hyb", "seg")))
    assert het.data.shape[0] == 4 and het.cols.shape == het.data.shape
    assert het.seg_vals is None                 # not a uniform-seg program
    seg = lower(A, SpmvPlan(num_shards=4, kernel="seg"))
    assert seg.seg_vals is not None and seg.seg_pieces.shape[-1] == 4
    from repro.core.spmv import DistributedSpmv, build_halo
    assert isinstance(het, DistributedSpmv)     # deprecated alias
    h = build_halo(het)
    assert h.halo >= 1 and h.send_idx.shape[:2] == (4, 4)


_SUBPROC = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import json
    import jax, jax.numpy as jnp, numpy as np
    from repro.core.program import execute, lower, make_program_spmv_fn, \\
        gather_b
    from repro.core.sparse_matrix import csr_matvec
    from repro.core.spmv import SpmvPlan
    from repro.data.matrices import make_matrix
    from jax.sharding import AxisType

    mesh = jax.make_mesh((4,), ("model",), axis_types=(AxisType.Auto,))
    A = make_matrix("cop20k_A", scale=0.003)
    x = np.random.default_rng(1).standard_normal(A.ncols).astype(np.float32)
    X = np.random.default_rng(2).standard_normal((A.ncols, 3)) \\
        .astype(np.float32)
    ref = csr_matvec(A, x)
    out = {}
    # executor equivalence: numpy oracle vs shard_map (jnp oracle) vs
    # shard_map (Pallas interpret), on a cross-section of the
    # exchange x layout x distribution x per-shard-kernel grid (the full
    # grid is pinned in-process against the numpy backend; the device
    # backend compiles, so it samples every axis value instead)
    bases = (("allgather", "block", "row"),
             ("allgather", "cyclic", "nonzero"),
             ("halo", "block", "nonzero"),
             ("halo", "cyclic", "row"))
    for exch, layout, dist_s in bases:
        for sk in (None, ("ell", "seg", "hyb", "seg"),
                   ("ell", "split", "hyb", "seg"),
                   ("tile", "seg", "split", "tile")):
            plan = SpmvPlan(layout=layout, distribution=dist_s,
                            exchange=exch, kernel="seg",
                            shard_kernels=sk, num_shards=4)
            prog = lower(A, plan)
            y_np = execute(prog, x)
            y_sm = execute(prog, x, backend="shard_map", mesh=mesh)
            tag = "seg" if sk is None else \\
                ("het+tile" if "tile" in sk else
                 "het+split" if "split" in sk else "het")
            key = f"{exch}/{layout}/{dist_s}/{tag}"
            out[key] = bool(
                np.allclose(y_np, ref, atol=1e-3) and
                np.allclose(y_sm, ref, atol=1e-3) and
                np.allclose(y_sm, y_np, atol=1e-3))
    # Pallas-interpret kernels through the same executor
    plan = SpmvPlan(exchange="halo", num_shards=4,
                    shard_kernels=("ell", "seg", "hyb", "seg"))
    prog = lower(A, plan)
    y_pal = execute(prog, x, backend="shard_map", mesh=mesh,
                    use_kernel=True)
    out["pallas"] = bool(np.allclose(y_pal, ref, atol=1e-3))
    # batched (N, B) through the device path
    Y = execute(prog, X, backend="shard_map", mesh=mesh)
    out["batched"] = bool(np.allclose(Y, csr_matvec(A, X), atol=1e-3))
    # reusable compiled fn + shard-form output
    fn = make_program_spmv_fn(prog, mesh)
    with mesh:
        ys = fn(jnp.asarray(prog.x_to_device(x)))
    out["fn_form"] = bool(np.allclose(gather_b(prog, ys), ref, atol=1e-3))
    # monster-row shards through the device split path (jnp oracle,
    # Pallas interpret, and batched), vs the numpy backend and csr_matvec
    from repro.data.matrices import powerlaw_tail
    Am = powerlaw_tail(1024, 2 * 4 * 1024, n_monster=4, seed=3)
    xm = np.random.default_rng(3).standard_normal(Am.ncols) \\
        .astype(np.float32)
    refm = csr_matvec(Am, xm)
    pm = lower(Am, SpmvPlan(num_shards=4, distribution="nonzero",
                            shard_kernels=("split", "split", "seg", "seg")))
    y_np = execute(pm, xm)
    y_sm = execute(pm, xm, backend="shard_map", mesh=mesh)
    y_pk = execute(pm, xm, backend="shard_map", mesh=mesh,
                   use_kernel=True)
    out["monster_split"] = bool(
        np.allclose(y_np, refm, atol=1e-2) and
        np.allclose(y_sm, refm, atol=1e-2) and
        np.allclose(y_pk, refm, atol=1e-2))
    Xm = np.random.default_rng(4).standard_normal((Am.ncols, 3)) \\
        .astype(np.float32)
    Ym = execute(pm, Xm, backend="shard_map", mesh=mesh)
    out["monster_split_batched"] = bool(
        np.allclose(Ym, csr_matvec(Am, Xm), atol=1e-2))
    # blocked-band shards through the device tile path (jnp oracle,
    # Pallas interpret, and batched), mixed with the split family
    from repro.data.matrices import blocked_band
    At = blocked_band(512, 215 * 512, seed=0)
    xt = np.random.default_rng(8).standard_normal(At.ncols) \\
        .astype(np.float32)
    reft = csr_matvec(At, xt)
    pt = lower(At, SpmvPlan(num_shards=4, exchange="halo",
                            shard_kernels=("tile", "tile", "split", "seg")))
    y_np = execute(pt, xt)
    y_sm = execute(pt, xt, backend="shard_map", mesh=mesh)
    y_pk = execute(pt, xt, backend="shard_map", mesh=mesh,
                   use_kernel=True)
    out["blocked_tile"] = bool(
        np.allclose(y_np, reft, atol=1e-2) and
        np.allclose(y_sm, reft, atol=1e-2) and
        np.allclose(y_pk, reft, atol=1e-2))
    Xt = np.random.default_rng(9).standard_normal((At.ncols, 3)) \\
        .astype(np.float32)
    Yt = execute(pt, Xt, backend="shard_map", mesh=mesh)
    out["blocked_tile_batched"] = bool(
        np.allclose(Yt, csr_matvec(At, Xt), atol=1e-2))
    # empty shards on the device path, all five families (the 6x6 matrix
    # leaves zero-nnz shards, so the tile stage here is the zero-tile
    # no-op slab)
    from repro.core.sparse_matrix import csr_from_coo
    Ad = csr_from_coo([0, 0, 5], [1, 4, 0], [2.0, -1.0, 3.0], (6, 6))
    xd = np.arange(6, dtype=np.float32)
    refd = csr_matvec(Ad, xd)
    for kern in ("ell", "seg", "hyb", "split", "tile"):
        pd = lower(Ad, SpmvPlan(kernel=kern, num_shards=4))
        yd = execute(pd, xd, backend="shard_map", mesh=mesh)
        out[f"empty_{kern}"] = bool(np.allclose(yd, refd, atol=1e-5))
    print(json.dumps(out))
""")


@pytest.mark.slow
def test_executor_equivalence_4dev_subprocess():
    r = subprocess.run([sys.executable, "-c", _SUBPROC], capture_output=True,
                       text=True, timeout=600,
                       env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin"})
    assert r.returncode == 0, r.stderr[-2000:]
    res = json.loads(r.stdout.strip().splitlines()[-1])
    assert all(res.values()), res


_SUBPROC_PIPELINE = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import json
    import jax, numpy as np
    from repro.core.program import execute, lower
    from repro.core.sparse_matrix import csr_matvec
    from repro.core.spmv import SpmvPlan
    from repro.data.matrices import make_matrix, mixed_structure, \\
        powerlaw_tail
    from jax.sharding import AxisType

    mesh = jax.make_mesh((4,), ("model",), axis_types=(AxisType.Auto,))
    A_mixed = mixed_structure(1024, 1024 * 8, seed=0)
    A_tail = powerlaw_tail(1024, 2 * 4 * 1024, n_monster=4, seed=3)
    A_cop = make_matrix("cop20k_A", scale=0.003)
    # "drifted" cop20k_A: the serving-path failure mode — an ordering
    # artifact scrambles the ingest-time structure out from under the plan
    perm = np.random.default_rng(7).permutation(A_cop.nrows)
    A_drift = A_cop.permuted(perm, perm)

    cases = {
        "mixed_structure": (A_mixed, SpmvPlan(
            num_shards=4, exchange="halo",
            shard_kernels=("ell", "seg", "hyb", "split"))),
        "mixed_structure_mixed_exchange": (A_mixed, SpmvPlan(
            num_shards=4, exchange="halo", kernel="seg",
            shard_exchanges=("halo", "allgather", "halo", "allgather"))),
        "powerlaw_tail": (A_tail, SpmvPlan(
            num_shards=4, distribution="nonzero",
            shard_kernels=("split", "split", "seg", "seg"))),
        "cop20k_A_drifted_allgather": (A_drift, SpmvPlan(
            num_shards=4, exchange="allgather", kernel="seg")),
        "cop20k_A_drifted_halo": (A_drift, SpmvPlan(
            num_shards=4, exchange="halo", kernel="hyb",
            layout="cyclic", distribution="nonzero")),
    }
    out = {}
    for name, (A, plan) in cases.items():
        x = np.random.default_rng(5).standard_normal(A.ncols) \\
            .astype(np.float32)
        X = np.random.default_rng(6).standard_normal((A.ncols, 3)) \\
            .astype(np.float32)
        prog = lower(A, plan)
        ref = csr_matvec(A, x)
        y_pipe = np.asarray(execute(prog, x, backend="shard_map",
                                    mesh=mesh))
        y_ser = np.asarray(execute(prog, x, backend="shard_map", mesh=mesh,
                                   pipeline=False))
        Y_pipe = np.asarray(execute(prog, X, backend="shard_map",
                                    mesh=mesh))
        Y_ser = np.asarray(execute(prog, X, backend="shard_map", mesh=mesh,
                                   pipeline=False))
        out[name] = bool(np.array_equal(y_pipe, y_ser) and
                         np.array_equal(Y_pipe, Y_ser) and
                         np.allclose(y_pipe, ref, atol=1e-2, rtol=1e-4))
    # Pallas-interpret kernels: the pipelined and serial schedules feed
    # the same kernel bodies, so bitwise equality must hold there too
    xk = np.random.default_rng(5).standard_normal(A_mixed.ncols) \\
        .astype(np.float32)
    prog = lower(A_mixed, SpmvPlan(
        num_shards=4, exchange="halo",
        shard_kernels=("ell", "seg", "hyb", "seg")))
    y_pipe = np.asarray(execute(prog, xk, backend="shard_map", mesh=mesh,
                                use_kernel=True))
    y_ser = np.asarray(execute(prog, xk, backend="shard_map", mesh=mesh,
                               use_kernel=True, pipeline=False))
    out["pallas_interpret_bitwise"] = bool(
        np.array_equal(y_pipe, y_ser) and
        np.allclose(y_pipe, csr_matvec(A_mixed, xk), atol=1e-2, rtol=1e-4))
    print(json.dumps(out))
""")


@pytest.mark.slow
def test_pipelined_executor_bitwise_equals_serial_4dev_subprocess():
    """The pipelined schedule (local slice overlapping the exchange) must
    be bitwise-identical to the pre-pipeline serial execution order on
    every workload/backend — the serial path runs the identical slice
    split behind an optimization barrier, so any divergence is a real
    operand bug, not float reassociation."""
    r = subprocess.run([sys.executable, "-c", _SUBPROC_PIPELINE],
                       capture_output=True, text=True, timeout=600,
                       env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin"})
    assert r.returncode == 0, r.stderr[-2000:]
    res = json.loads(r.stdout.strip().splitlines()[-1])
    assert all(res.values()), res


_SUBPROC_PASSES = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import json, re
    import jax, numpy as np
    from repro.core.program import _device_operands, build_program_step, \\
        execute, lower, make_program_spmv_fn
    from repro.core.sparse_matrix import csr_from_coo, csr_matvec
    from repro.core.spmv import SpmvPlan
    from repro.data.matrices import make_matrix
    from jax.sharding import AxisType

    mesh = jax.make_mesh((4,), ("model",), axis_types=(AxisType.Auto,))
    # block diagonal, one 128-row block per shard under an even row split
    rng = np.random.default_rng(0)
    blk, r, c = (rng.integers(0, k, 3000) for k in (4, 128, 128))
    A_bd = csr_from_coo(blk * 128 + r, blk * 128 + c,
                        rng.standard_normal(3000), (512, 512))
    A_cop = make_matrix("cop20k_A", scale=0.003)
    cases = {
        "halo": (A_cop, SpmvPlan(num_shards=4, exchange="halo",
                                 kernel="hyb")),
        "allgather": (A_cop, SpmvPlan(num_shards=4, exchange="allgather",
                                      kernel="hyb")),
        "block_diagonal": (A_bd, SpmvPlan(num_shards=4, exchange="halo",
                                          kernel="seg", distribution="row")),
    }
    out = {}
    for name, (A, plan) in cases.items():
        prog = lower(A, plan)
        fn = make_program_spmv_fn(prog, mesh)
        ops = _device_operands(prog)
        step, host_ops = build_program_step(prog, mesh)
        xs = np.zeros((4, prog.x_layout.padded_length() // 4), np.float32)
        text = step.lower(*host_ops, xs).as_text(debug_info=True)
        x = np.random.default_rng(5).standard_normal(A.ncols) \\
            .astype(np.float32)
        y = np.asarray(execute(prog, x, backend="shard_map", mesh=mesh))
        y_ser = np.asarray(execute(prog, x, backend="shard_map", mesh=mesh,
                                   pipeline=False))
        out[name] = dict(
            passes=fn.passes,
            rem_keys=sum(k.startswith("rem_") for k in ops),
            tables=sorted(k for k in ("send_idx", "row_remote") if k in ops),
            collectives=sorted(set(re.findall(
                r"stablehlo\\.(all_to_all|all_gather)", text))),
            scopes=[s for s in ("spmv.exchange", "spmv.local",
                                "spmv.remote", "spmv.combine") if s in text],
            matches=bool(np.allclose(y, csr_matvec(A, x), atol=1e-3,
                                     rtol=1e-4)),
            pipelined_equals_serial=bool(np.array_equal(y, y_ser)))
    print(json.dumps(out))
""")


@pytest.mark.slow
def test_remote_reads_decide_the_kernel_passes_4dev_subprocess():
    """On four devices a program with remote reads keeps both slices, its
    remote operand set, the exchange and the combine; a block-diagonal
    program, whose rows read only their own shard's x, runs one pass with
    no collective."""
    r = subprocess.run([sys.executable, "-c", _SUBPROC_PASSES],
                       capture_output=True, text=True, timeout=600,
                       env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin"})
    assert r.returncode == 0, r.stderr[-2000:]
    res = json.loads(r.stdout.strip().splitlines()[-1])
    two = ["spmv.exchange", "spmv.local", "spmv.remote", "spmv.combine"]
    for name, collective in (("halo", "all_to_all"),
                             ("allgather", "all_gather")):
        assert res[name] == dict(
            passes=2, rem_keys=5, tables=["row_remote", "send_idx"],
            collectives=[collective], scopes=two, matches=True,
            pipelined_equals_serial=True), name
    assert res["block_diagonal"] == dict(
        passes=1, rem_keys=0, tables=[], collectives=[],
        scopes=["spmv.local"], matches=True, pipelined_equals_serial=True)


# --------------------------------------------------------------------------
# the device executor on a 1-device mesh (in-process; interpret mode)
# --------------------------------------------------------------------------

def _mesh1():
    import jax
    from jax.sharding import AxisType
    return jax.make_mesh((1,), ("model",), axis_types=(AxisType.Auto,),
                         devices=jax.devices()[:1])


def test_execute_rejects_mesh_of_wrong_size():
    """A 4-shard program on a 1-device mesh used to die in gather_b with an
    IndexError; the executor now refuses it up front."""
    from repro.core.program import make_program_spmv_fn
    A = make_matrix("ford1", scale=0.05)
    prog = lower(A, SpmvPlan(num_shards=4))
    with pytest.raises(ValueError, match="4 shards"):
        execute(prog, np.zeros(A.ncols), backend="shard_map", mesh=_mesh1())
    with pytest.raises(ValueError, match="4 shards"):
        make_program_spmv_fn(prog, _mesh1())
    with pytest.raises(ValueError, match="no axis"):
        make_program_spmv_fn(lower(A, SpmvPlan(num_shards=1)), _mesh1(),
                             axis="data")


def test_interpret_follows_the_platform(monkeypatch):
    """Interpret mode is derived from the mesh's platform and cannot be
    asked for: on a (faked) TPU mesh the kernels trace compiled, and an
    ``interpret`` argument is refused."""
    import jax
    import repro.core.program as program
    from repro.core.program import build_program_step, kernel_interpret, \
        make_program_spmv_fn
    assert kernel_interpret("cpu") is True
    assert kernel_interpret("tpu") is False
    A = make_matrix("ford1", scale=0.05)
    prog = lower(A, SpmvPlan(kernel="ell", num_shards=1))
    monkeypatch.setattr(program, "_mesh_platform", lambda mesh: "tpu")
    with pytest.raises(TypeError, match="interpret"):
        make_program_spmv_fn(prog, _mesh1(), use_kernel=True,
                             interpret=True)
    with pytest.raises(TypeError, match="interpret"):
        execute(prog, np.zeros(A.ncols), backend="shard_map",
                mesh=_mesh1(), use_kernel=True, interpret=True)
    seen = []

    def ell_spy(data, cols, x, *, interpret):
        seen.append(interpret)
        return program.kops.ell_spmv_ref(data, cols, x)
    monkeypatch.setattr(program.kops, "ell_spmv", ell_spy)
    step, operands = build_program_step(prog, _mesh1(), use_kernel=True)
    x = np.zeros((1, prog.x_layout.padded_length()), np.float32)
    jax.eval_shape(step, *operands, x)
    assert seen and not any(seen)


@pytest.mark.parametrize("kernel", ["ell", "seg", "hyb", "split", "tile"])
@pytest.mark.parametrize("reordering", ["none", "bfs"])
def test_single_device_executor_matches_oracle(kernel, reordering):
    """One shard on one device, jnp and Pallas paths, 1-D and (N, B):
    ``scatter_x`` applies the reordering that ``gather_b`` undoes."""
    from repro.core.program import gather_b, make_program_spmv_fn, scatter_x
    A = make_matrix("cop20k_A", scale=0.005)
    prog = lower(A, SpmvPlan(kernel=kernel, reordering=reordering,
                             num_shards=1))
    assert (prog.perm is None) == (reordering == "none")
    rng = np.random.default_rng(11)
    x = rng.standard_normal(A.ncols)
    X = rng.standard_normal((A.ncols, 3))
    for use_kernel in (False, True):
        fn = make_program_spmv_fn(prog, _mesh1(), use_kernel=use_kernel)
        assert fn.program is prog and fn.operand_bytes > 0
        for v in (x, X):
            y = gather_b(prog, fn(scatter_x(prog, v)))
            np.testing.assert_allclose(y, csr_matvec(A, v), atol=1e-4,
                                       rtol=1e-4)
    np.testing.assert_allclose(
        execute(prog, x, backend="shard_map", mesh=_mesh1()),
        csr_matvec(A, x), atol=1e-4, rtol=1e-4)


_FAMILY_KEYS = {
    "ell": ("ell_data", "ell_cols"),
    "hyb": ("ell_data", "ell_cols", "ovf_rows", "ovf_cols", "ovf_vals"),
    "seg": ("seg_vals", "seg_cols", "seg_starts", "seg_pieces"),
    "split": ("seg_vals", "seg_cols", "seg_starts", "seg_pieces"),
    "tile": ("tile_data", "tile_xcol", "tile_brow"),
}


def _eqns_in(jaxpr):
    """Every equation of ``jaxpr`` and of the jaxprs nested in it
    (shard_map, jit, the ``lax.switch`` branches)."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _eqns_in(sub)


@pytest.mark.parametrize("kernel", ["ell", "seg", "hyb", "split", "tile"])
def test_one_shard_programs_run_one_kernel_pass(kernel):
    """One shard reads no remote x entry, so the step gets the local
    operand set alone and one pass: no remote set, exchange tables,
    exchange, remote slice or combine, and the same answers.  It runs
    one family, so its set holds that family's formats alone, with no
    ``kid``, and the step calls the family's pass with no ``cond``."""
    import jax
    from repro.core.program import _device_operands, _operand_keys, \
        build_program_step, gather_b, make_program_spmv_fn, scatter_x
    A = make_matrix("cop20k_A", scale=0.003)
    prog = lower(A, SpmvPlan(kernel=kernel, num_shards=1))
    fn = make_program_spmv_fn(prog, _mesh1())
    ops = _device_operands(prog)
    assert fn.passes == ops["passes"] == 1
    assert not any(k.startswith("rem_") for k in ops)
    assert "send_idx" not in ops and "row_remote" not in ops
    keys = tuple("loc_" + k for k in _FAMILY_KEYS[kernel])
    assert _operand_keys(ops) == keys and "kid" not in ops
    assert fn.operand_bytes == sum(ops[k].nbytes for k in keys)
    step, host_ops = build_program_step(prog, _mesh1())
    xs = np.zeros((1, prog.x_layout.padded_length()), np.float32)
    jaxpr = jax.make_jaxpr(step)(*host_ops, xs).jaxpr
    assert not any(e.primitive.name == "cond" for e in _eqns_in(jaxpr))
    text = step.lower(*host_ops, xs).as_text(debug_info=True)
    assert f"spmv.local/{kernel}" in text
    for absent in ("spmv.exchange", "spmv.remote", "spmv.combine",
                   "all_gather", "all_to_all"):
        assert absent not in text, absent
    rng = np.random.default_rng(12)
    for v in (rng.standard_normal(A.ncols),
              rng.standard_normal((A.ncols, 3))):
        y = gather_b(prog, fn(scatter_x(prog, v)))
        np.testing.assert_allclose(y, csr_matvec(A, v), atol=1e-4,
                                   rtol=1e-4)
        y_ser = execute(prog, v, backend="shard_map", mesh=_mesh1(),
                        pipeline=False)
        np.testing.assert_array_equal(y, y_ser)


def _scatter_updates_in(jaxpr) -> list:
    """The update counts of every scatter-add in ``jaxpr`` and the jaxprs
    nested in it."""
    return [int(np.prod(e.invars[2].aval.shape)) for e in _eqns_in(jaxpr)
            if e.primitive.name == "scatter-add"]


def test_one_pass_program_stacks_the_lowered_stages(monkeypatch):
    """A program with no remote reads stacks ``program.stages`` as
    ``lower`` built them: building its operands converts no shard
    again."""
    import repro.core.program as program
    calls = []
    hyb_from_csr = program.kops.hyb_from_csr
    monkeypatch.setattr(program.kops, "hyb_from_csr",
                        lambda csr: calls.append(csr) or hyb_from_csr(csr))
    A = make_matrix("cop20k_A", scale=0.003)
    prog = lower(A, SpmvPlan(kernel="hyb", num_shards=1))
    assert len(calls) == 1
    ops = program._device_operands(prog)
    assert ops["passes"] == 1 and len(calls) == 1
    st = prog.stages[0]
    np.testing.assert_array_equal(
        ops["loc_ell_data"][0, : st.ell.data.shape[0]], st.ell.data)


def test_one_shard_seg_step_scatters_pieces_not_slots():
    """The served seg pass sums rows by segmented chunk scans and one
    scatter update a piece: the step holds no scatter over the slab's C·L
    slots, the seg branch's scatter has ``n_pieces`` updates, and no
    operand carries per-slot row ids."""
    import jax
    from repro.core.program import _device_operands, _operand_keys, \
        build_program_step, make_program_spmv_fn
    A = make_matrix("cop20k_A", scale=0.003)
    prog = lower(A, SpmvPlan(kernel="seg", num_shards=1))
    ops = _device_operands(prog)
    assert "loc_seg_rows" not in _operand_keys(ops)
    assert not any("seg_rows" in k for k in ops)
    n_pieces = prog.stages[0].seg.n_pieces
    C, L = ops["loc_seg_vals"].shape[1:]
    assert n_pieces < C * L
    step, host_ops = build_program_step(prog, _mesh1())
    xs = np.zeros((1, prog.x_layout.padded_length()), np.float32)
    updates = _scatter_updates_in(jax.make_jaxpr(step)(*host_ops, xs).jaxpr)
    assert C * L not in updates
    assert n_pieces in updates
    fn = make_program_spmv_fn(prog, _mesh1())
    assert fn.kernel_counts == dict(scatter_updates=[n_pieces])


def _x_gathers(jaxpr, per: int) -> list:
    """The operand shapes of the gathers of the shard's x buffer, (per,)
    or (per, B), in ``jaxpr`` and the jaxprs nested in it."""
    return [e.invars[0].aval.shape for e in _eqns_in(jaxpr)
            if e.primitive.name == "gather"
            and e.invars[0].aval.shape[:1] == (per,)]


@pytest.mark.parametrize("kernel", ["ell", "seg", "hyb", "split", "tile"])
def test_block_step_gathers_x_once_in_chunk_families(kernel):
    """The (n, B) step of a seg or split program hands the block whole to
    the chunk pass: no loop over B, and one gather of x's B-wide rows.
    The ell, hyb and tile steps still loop over the vectors, gathering
    (n,) x in the loop, and every (n,) step gathers (n,) x with no loop."""
    import jax
    from repro.core.program import build_program_step
    A = make_matrix("cop20k_A", scale=0.003)
    prog = lower(A, SpmvPlan(kernel=kernel, num_shards=1))
    step, host_ops = build_program_step(prog, _mesh1())
    per, B = prog.x_layout.padded_length(), 8
    one = jax.make_jaxpr(step)(*host_ops, np.zeros((1, per), np.float32))
    assert not any(e.primitive.name in ("scan", "while")
                   for e in _eqns_in(one.jaxpr))
    assert set(_x_gathers(one.jaxpr, per)) == {(per,)}
    block = jax.make_jaxpr(step)(*host_ops,
                                 np.zeros((1, per, B), np.float32)).jaxpr
    loops_over_b = [e for e in _eqns_in(block) if e.primitive.name == "scan"
                    and e.params["length"] == B]
    if kernel in ("seg", "split"):
        assert not loops_over_b
        assert _x_gathers(block, per) == [(per, B)]
    else:
        assert len(loops_over_b) == 1
        assert set(_x_gathers(block, per)) == {(per,)}


@pytest.mark.parametrize("kernel", ["seg", "split"])
@pytest.mark.parametrize("B", [2, 8])
def test_one_shard_block_step_columns_bitwise(kernel, B, monkeypatch):
    """A one-shard seg or split program's (n, B) step, in one group of
    chunks and in several (a budget of two chunks), returns each column
    of the block bitwise as the (n,) step returns that vector."""
    from repro.core.program import _device_operands, gather_b, \
        make_program_spmv_fn, scatter_x
    from repro.kernels import ops as kops
    A = make_matrix("cop20k_A", scale=0.003)
    prog = lower(A, SpmvPlan(kernel=kernel, num_shards=1))
    C, L = _device_operands(prog)["loc_seg_vals"].shape[1:]
    X = np.random.default_rng(B).standard_normal((A.ncols, B))
    for budget in (kops.SEG_GROUP_BYTES, 2 * L * 128 * 4):
        monkeypatch.setattr(kops, "SEG_GROUP_BYTES", budget)
        assert kops._seg_groups(C, L, B)[1] == (1 if budget > 2**20
                                                else -(-C // 2))
        fn = make_program_spmv_fn(prog, _mesh1())
        Y = gather_b(prog, fn(scatter_x(prog, X)))
        for b in range(B):
            np.testing.assert_array_equal(
                Y[:, b], gather_b(prog, fn(scatter_x(prog, X[:, b]))))
        np.testing.assert_allclose(Y, csr_matvec(A, X), atol=1e-4,
                                   rtol=1e-4)


_SUBPROC_SEG4 = textwrap.dedent("""
    import os, sys
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import json
    import jax, numpy as np
    from repro.core.program import execute, lower, make_program_spmv_fn
    from repro.core.sparse_matrix import csr_matvec
    from repro.core.spmv import SpmvPlan
    from repro.data.matrices import make_matrix
    from repro.kernels import ops
    from jax.sharding import AxisType

    # a group holds two chunks: each pass's slab spans several
    ops.SEG_GROUP_BYTES = 2 * ops.SEG_CHUNK * 128 * 4
    shard_kernels = json.loads(sys.argv[1])
    mesh = jax.make_mesh((4,), ("model",), axis_types=(AxisType.Auto,))
    A = make_matrix("cop20k_A", scale=0.003)
    prog = lower(A, SpmvPlan(num_shards=4, kernel="seg", exchange="halo",
                             distribution="nonzero",
                             shard_kernels=shard_kernels))
    fn = make_program_spmv_fn(prog, mesh)
    out = dict(passes=fn.passes, kernels=list(prog.shard_kernels()),
               matches=True, columns_bitwise=True)
    for B in (2, 8):
        X = np.random.default_rng(6 + B).standard_normal((A.ncols, B)) \\
            .astype(np.float32)
        Y = np.asarray(execute(prog, X, backend="shard_map", mesh=mesh))
        cols = [np.asarray(execute(prog, X[:, b], backend="shard_map",
                                   mesh=mesh)) for b in range(B)]
        out["matches"] &= bool(
            np.allclose(Y, execute(prog, X), atol=1e-4, rtol=1e-4)
            and np.allclose(Y, csr_matvec(A, X), atol=1e-4, rtol=1e-4))
        out["columns_bitwise"] &= all(np.array_equal(Y[:, b], cols[b])
                                      for b in range(B))
    print(json.dumps(out))
""")


@pytest.mark.slow
@pytest.mark.parametrize("shard_kernels", [None, ["seg", "hyb", "seg",
                                                  "hyb"]])
def test_four_shard_two_pass_seg_matches_numpy_4dev_subprocess(
        shard_kernels):
    """The served seg pass in both kernel passes of a sharded program, on
    its own and beside hyb shards behind the step's switch: answers match
    the numpy backend and the float64 product, and the columns of (n, 2)
    and (n, 8) blocks, walked in several groups of chunks, equal their
    per-vector runs bitwise."""
    r = subprocess.run([sys.executable, "-c", _SUBPROC_SEG4,
                        json.dumps(shard_kernels)],
                       capture_output=True, text=True, timeout=600,
                       env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin"})
    assert r.returncode == 0, r.stderr[-2000:]
    res = json.loads(r.stdout.strip().splitlines()[-1])
    assert res == dict(passes=2, kernels=shard_kernels or ["seg"] * 4,
                       matches=True, columns_bitwise=True)
