"""The kernel counters of a served tenant (``stats()[t]["kernels"]``)
against an independent count made from the CSR alone.

``scatter_updates`` is, per shard, the scatter-add updates one vector's
step runs, summed over its kernel passes: a (row, chunk) piece each for
seg and split, an overflow entry each for hyb, none for ell and tile.
``operand_reads`` sums the operand-set reads of the served requests on a
shard, counted in ``requests``.
One shard is served in-process on one CPU device; a four-shard program
with remote reads, whose step runs two passes, on four virtual CPU
devices in a subprocess."""
import json
import subprocess
import sys
import textwrap
from pathlib import Path

import numpy as np
import pytest

from repro.core.sparse_matrix import hyb_cap_width
from repro.kernels.ops import SEG_CHUNK

ROOT = Path(__file__).resolve().parents[1]


def expected_updates(sub, keep, kernel: str, chunk: int = SEG_CHUNK) -> int:
    """Scatter-add updates of the rows ``keep`` of shard CSR ``sub``: the
    distinct (row, chunk) pairs of the kept entries laid end to end in
    row order for seg and split, the entries past the p95 width for hyb."""
    rows = np.repeat(np.arange(sub.nrows), np.diff(sub.row_ptr))
    kept = rows[np.asarray(keep)[rows]]
    if kernel in ("seg", "split"):
        return len(set(zip(kept.tolist(),
                           (np.arange(kept.size) // chunk).tolist())))
    if kernel == "hyb":
        per_row = np.bincount(kept, minlength=sub.nrows)
        return int(np.maximum(per_row - hyb_cap_width(per_row), 0).sum())
    return 0


@pytest.mark.parametrize("kernel", ["seg", "split", "hyb", "ell", "tile"])
def test_one_shard_counts_its_pieces_or_overflow(kernel):
    import jax
    from jax.sharding import AxisType
    from repro.core.spmv import SpmvPlan
    from repro.data.matrices import powerlaw
    from repro.serve.router import SparseMatrixEngine
    A = powerlaw(1024, 40_000, seed=2)
    mesh = jax.make_mesh((1,), ("model",), axis_types=(AxisType.Auto,),
                         devices=jax.devices()[:1])
    eng = SparseMatrixEngine(mesh=mesh)
    eng.ingest("t", A, SpmvPlan(kernel=kernel, num_shards=1))
    counts = eng.stats()["t"]["kernels"]["scatter_updates"]
    want = expected_updates(A, np.ones(A.nrows, bool), kernel)
    assert counts == [want]
    if kernel in ("seg", "split"):
        stage = eng.device_fn("t").program.stages[0]
        assert want == getattr(stage, kernel).n_pieces
        assert want < A.nnz
    if kernel == "hyb":
        assert want > 0                    # the matrix really overflows


@pytest.mark.parametrize("kernel", ["seg", "split", "hyb", "ell", "tile"])
def test_one_shard_counts_operand_reads_per_request(kernel):
    """``operand_reads``: the operand-set reads of the served requests on
    a shard, ``requests`` beside it.  A vector costs one read; an (n, B)
    block one where the family takes it whole (seg, split), B where its
    pass loops over the vectors (hyb, ell, tile)."""
    import jax
    from jax.sharding import AxisType
    from repro.core.spmv import SpmvPlan
    from repro.data.matrices import powerlaw
    from repro.serve.router import SparseMatrixEngine
    A = powerlaw(1024, 8_000, seed=2)
    mesh = jax.make_mesh((1,), ("model",), axis_types=(AxisType.Auto,),
                         devices=jax.devices()[:1])
    eng = SparseMatrixEngine(mesh=mesh)
    eng.ingest("t", A, SpmvPlan(kernel=kernel, num_shards=1))
    fn = eng.device_fn("t")
    B = 8
    per_block = 1 if kernel in ("seg", "split") else B
    assert (fn.operand_reads(1), fn.operand_reads(B)) == (1, per_block)
    k = eng.stats()["t"]["kernels"]
    assert (k["operand_reads"], k["requests"]) == (0, 0)
    rng = np.random.default_rng(4)
    eng.spmv("t", rng.standard_normal(A.ncols))
    eng.spmv("t", rng.standard_normal((A.ncols, B)))
    k = eng.stats()["t"]["kernels"]
    assert (k["operand_reads"], k["requests"]) == (1 + per_block, 2)


SCRIPT = textwrap.dedent("""
    import json, sys
    from pathlib import Path
    root = Path(sys.argv[1])
    sys.path[:0] = [str(root / "src"), str(root / "tests")]
    import jax, numpy as np
    from jax.sharding import AxisType
    from repro.core.program import _device_operands, execute
    from repro.core.sparse_matrix import csr_matvec
    from repro.core.spmv import SpmvPlan
    from repro.data.matrices import powerlaw_tail
    from repro.serve.router import SparseMatrixEngine
    from test_kernel_counters import expected_updates

    # four monster rows: the nonzero split gives them the first shards,
    # and the hyb shard's rows spill past its p95 width
    A = powerlaw_tail(1024, 2 * 4 * 1024, n_monster=4, seed=3)
    mesh = jax.make_mesh((4,), ("model",), axis_types=(AxisType.Auto,))
    eng = SparseMatrixEngine(mesh=mesh)
    out = {}
    for name, sk in (("seg", None), ("mixed", ("split", "hyb", "seg",
                                               "ell"))):
        eng.ingest(name, A, SpmvPlan(num_shards=4, kernel="seg",
                                     shard_kernels=sk, exchange="halo",
                                     distribution="nonzero"))
        s = eng.stats()[name]
        prog = eng.device_fn(name).program
        ops = _device_operands(prog)
        want = []
        for p, st in enumerate(prog.stages):
            sub = prog.partition.shard_csr(prog.matrix, p)
            rr = ops["row_remote"][p, : st.rows]
            want.append(expected_updates(sub, ~rr, st.kernel)
                        + expected_updates(sub, rr, st.kernel))
        x = np.random.default_rng(7).standard_normal(A.ncols) \\
            .astype(np.float32)
        y = np.asarray(eng.spmv(name, x))
        reads = [eng.stats()[name]["kernels"]["operand_reads"]]
        eng.spmv(name, np.stack([x] * 3, axis=1))
        k = eng.stats()[name]["kernels"]
        out[name] = dict(
            passes=s["device_passes"], kernels=list(prog.shard_kernels()),
            reads=reads + [k["operand_reads"], k["requests"]],
            counts=s["kernels"]["scatter_updates"], want=want,
            matches=bool(np.allclose(y, execute(prog, x), atol=1e-3,
                                     rtol=1e-4)
                         and np.allclose(y, csr_matvec(A, x), atol=1e-3,
                                         rtol=1e-4)))
    print(json.dumps(out))
""")


@pytest.mark.slow
def test_four_shards_count_both_passes_4dev_subprocess():
    r = subprocess.run([sys.executable, "-c", SCRIPT, str(ROOT)],
                       capture_output=True, text=True, timeout=600,
                       env={"PATH": "/usr/bin:/bin", "JAX_PLATFORMS": "cpu",
                            "XLA_FLAGS":
                            "--xla_force_host_platform_device_count=4"})
    assert r.returncode == 0, r.stderr[-3000:]
    res = json.loads(r.stdout.strip().splitlines()[-1])
    # operand reads after one vector, then after a block of 3 besides:
    # two passes read once each a request where every family takes the
    # block whole, three times each where hyb and ell loop over it
    for name, kernels, reads in (("seg", ["seg"] * 4, [2, 4, 2]),
                                 ("mixed", ["split", "hyb", "seg", "ell"],
                                  [2, 8, 2])):
        got = res[name]
        assert got["passes"] == 2 and got["kernels"] == kernels, got
        assert got["reads"] == reads, got
        assert got["counts"] == got["want"], got
        assert got["matches"], got
    assert res["mixed"]["counts"][3] == 0          # ell scatters nothing
    assert res["mixed"]["counts"][1] > 0           # hyb spills
