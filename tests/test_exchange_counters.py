"""The exchange counters of a served tenant (``stats()[t]["exchange"]``)
against an independent count.

The four-chip benchmark configuration ``cop20k_A_synth.x4``, cut to about
1/100 as the chip benchmark's CPU tests cut it, is ingested on four
virtual CPU devices (in a subprocess): the autotuned program still runs
two kernel passes with a halo exchange on every shard, and its counters
equal a plain per-row count made from the partition, the layout and the
CSR.  The same matrix on one device is one shard and counts nothing."""
import json
import subprocess
import sys
import textwrap
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]

SCRIPT = textwrap.dedent("""
    import dataclasses, json, sys, tempfile
    from pathlib import Path
    root = Path(sys.argv[1])
    sys.path[:0] = [str(root), str(root / "src"),
                    str(root / "tests" / "chip_bench")]
    import jax
    from jax.sharding import AxisType
    from chip_bench.matrix import build_matrix
    from repro.core.program import _device_operands, make_program_spmv_fn
    from repro.core.program import lower
    from repro.core.sparse_matrix import CSRMatrix
    from repro.serve.router import SparseMatrixEngine
    from test_chip_bench_harness import small_bench

    bench = small_bench(Path(tempfile.mkdtemp()))
    spec = bench.config("cop20k_A_synth.x4")["tenants"][0]
    A = build_matrix(spec, 2 ** 33 + 21, 0)
    A = CSRMatrix(shape=A.shape, values=A.values.copy(),
                  col_index=A.col_index.copy(), row_ptr=A.row_ptr.copy())

    def mesh(n):
        return jax.make_mesh((n,), ("model",), axis_types=(AxisType.Auto,),
                             devices=jax.devices()[:n])

    def engine(n):
        eng = SparseMatrixEngine(mesh=mesh(n))
        eng.ingest("t", A)
        return eng

    def count(prog):
        # Row by row: a row reads remotely where one of its non-zero
        # entries' columns is owned by another shard than the row's.
        M, part, lay = prog.matrix, prog.partition, prog.x_layout
        S = part.num_shards
        shard_nnz, remote_rows, needed = [], [], set()
        for p in range(S):
            r0, r1 = int(part.starts[p]), int(part.starts[p + 1])
            shard_nnz.append(int(M.row_ptr[r1] - M.row_ptr[r0]))
            n = 0
            for r in range(r0, r1):
                lo, hi = M.row_ptr[r], M.row_ptr[r + 1]
                reads = [int(c) for c, v in zip(M.col_index[lo:hi],
                                                M.values[lo:hi])
                         if v != 0 and int(lay.owner_of(c)) != p]
                n += bool(reads)
                needed.update((p, c) for c in reads)
            remote_rows.append(n)
        halo = max([sum(1 for p2, c in needed if p2 == p and
                        int(lay.owner_of(c)) == q)
                    for p in range(S) for q in range(S)] + [1])
        return dict(shard_nnz=shard_nnz, remote_rows=remote_rows,
                    needed_entries=len(needed), halo=halo)

    eng4 = engine(4)
    s4 = eng4.stats()["t"]
    prog = eng4.device_fn("t").program
    R = _device_operands(prog)["R"]
    gather = make_program_spmv_fn(
        lower(A, dataclasses.replace(prog.plan, exchange="allgather")),
        mesh(4))
    s1 = engine(1).stats()["t"]
    print(json.dumps(dict(
        passes=s4["device_passes"], exchanges=s4["shard_exchanges"],
        exchange=s4["exchange"], expected=count(prog), R=R,
        rows=[int(n) for n in prog.rows_per_shard],
        per=prog.x_layout.padded_length() // 4,
        gather=gather.exchange, gather_passes=gather.passes,
        one=dict(passes=s1["device_passes"], exchange=s1["exchange"]))))
""")


def test_exchange_counters_equal_an_independent_count_4dev_subprocess():
    r = subprocess.run([sys.executable, "-c", SCRIPT, str(ROOT)],
                       capture_output=True, text=True, timeout=600,
                       env={"PATH": "/usr/bin:/bin", "JAX_PLATFORMS": "cpu",
                            "XLA_FLAGS":
                            "--xla_force_host_platform_device_count=4"})
    assert r.returncode == 0, r.stderr[-3000:]
    res = json.loads(r.stdout.strip().splitlines()[-1])
    ex, want = res["exchange"], res["expected"]

    # The cell keeps exercising what it exists for: two passes, a halo
    # exchange on every shard, rows that wait on it.
    assert res["passes"] == 2 and res["exchanges"] == ["halo"] * 4
    assert sum(ex["remote_rows"]) > 0

    assert ex["shard_nnz"] == want["shard_nnz"]
    assert ex["remote_rows"] == want["remote_rows"]
    assert ex["needed_entries"] == want["needed_entries"]
    assert ex["sent_entries"] == 4 * 4 * want["halo"]
    assert ex["sent_entries"] >= ex["needed_entries"]
    assert ex["remote_pass_rows"] == [res["R"]] * 4
    assert res["R"] >= max(res["rows"])

    # The same matrix under a uniform all-gather sends every shard's
    # padded slice to every shard, and reads the same remote entries.
    g = res["gather"]
    assert res["gather_passes"] == 2
    assert g["sent_entries"] == 4 * 4 * res["per"]
    assert g["needed_entries"] == want["needed_entries"]
    assert g["remote_rows"] == want["remote_rows"]

    # One shard: one pass and nothing counted.
    assert res["one"] == dict(passes=1, exchange=dict(
        sent_entries=0, needed_entries=0, remote_rows=[0],
        remote_pass_rows=[0], shard_nnz=[0]))
