"""Backend-equivalence harness for per-shard exchange policies.

Sweeps the full per-shard exchange x kernel grid against the float64
numpy oracle (``csr_matvec``), including batched ``(N, B)`` inputs,
degenerate zero-nnz shards, and single-shard meshes, plus a host-side
invariant on the device executor's exchange tables: rebuilding each
reader's ``[x_local ++ recv]`` buffer from the send tables in numpy must
reproduce the owner's x value at every mapped position — the exchange
machinery validated without a device mesh (the mesh-backed bitwise run
lives in ``test_program.py``'s subprocess tests; single-shard programs
also run here on a 1-device mesh).

Runs property-based when ``hypothesis`` is installed; falls back to a
deterministic seeded sweep of the same property otherwise, so every axis
is covered either way.
"""
import itertools

import numpy as np
import pytest

from repro.core.program import _device_operands, _halo_tables, \
    _operand_keys, _row_remote_flags, execute, lower
from repro.core.sparse_matrix import CSRMatrix, csr_from_coo, csr_matvec
from repro.core.spmv import PLAN_EXCHANGES, PLAN_KERNELS, SpmvPlan
from repro.data.matrices import mixed_structure

try:
    from hypothesis import HealthCheck, given, settings
    from hypothesis import strategies as hst
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False


def _random_csr(rng, M: int, density: float) -> CSRMatrix:
    n = max(int(M * M * density), 1)
    rows = rng.integers(0, M, n)
    cols = rng.integers(0, M, n)
    vals = rng.standard_normal(n)
    # a few explicit stored zeros — they must not widen the halo
    if n >= 4:
        vals[:2] = 0.0
    return csr_from_coo(rows, cols, vals, (M, M))


def _exchange_buffer_invariant(prog) -> None:
    """Host-side check of the all-to-all tables: every mapped position of
    every reader's augmented buffer holds the owner's x value."""
    S = prog.plan.num_shards
    if S == 1 or all(e == "allgather"
                     for e in prog.plan.resolved_shard_exchanges()):
        return
    lay = prog.x_layout
    rng = np.random.default_rng(99)
    x = rng.standard_normal(prog.matrix.ncols).astype(np.float32)
    xs = prog.x_to_device(x)                     # (S, per)
    send_idx, pos_map, H = _halo_tables(prog)
    per = xs.shape[1]
    for p in range(S):
        recv = np.stack([xs[q, send_idx[q, p]] for q in range(S)])
        aug = np.concatenate([xs[p], recv.reshape(-1)])
        need = np.flatnonzero(pos_map[p] >= per)  # global ids p receives
        if need.size == 0:
            continue
        own = lay.owner_of(need)
        loc = lay.local_index(need)
        np.testing.assert_array_equal(aug[pos_map[p, need]], xs[own, loc])


def _check_plan(A: CSRMatrix, plan: SpmvPlan, *, batch: bool = True) -> None:
    rng = np.random.default_rng(7)
    x = rng.standard_normal(A.ncols)
    prog = lower(A, plan)
    ref = csr_matvec(A, x)                       # float64 oracle
    y = execute(prog, x)
    np.testing.assert_allclose(y, ref, atol=2e-4, rtol=2e-4)
    if batch:
        X = rng.standard_normal((A.ncols, 3))
        Y = execute(prog, X)
        np.testing.assert_allclose(Y, csr_matvec(A, X), atol=2e-4,
                                   rtol=2e-4)
    # the device operand split must cover every stored entry exactly once
    ops = _device_operands(prog)
    loc_nnz = sum(st.nnz for st in prog.stages)
    assert all(ops[k].shape[0] == plan.num_shards
               for k in _operand_keys(ops))
    assert ("kid" in ops) == (len(set(prog.shard_kernels())) > 1)
    assert loc_nnz == A.nnz
    # a remote slice (and its selector) exists iff some row reads remotely
    remote = bool(_row_remote_flags(prog).any())
    assert ops["passes"] == 1 + remote
    assert ("row_remote" in ops) == remote
    if remote:
        assert ops["row_remote"].shape[0] == plan.num_shards
    _exchange_buffer_invariant(prog)


_KERNEL_CONFIGS = [
    ("ell", None), ("seg", None), ("hyb", None), ("split", None),
    ("tile", None),
    ("seg", ("ell", "seg", "hyb", "split")),
    ("tile", ("tile", "split", "tile", "ell")),
]


@pytest.mark.parametrize("exchanges",
                         list(itertools.product(PLAN_EXCHANGES, repeat=4)))
def test_full_per_shard_exchange_grid_vs_oracle(exchanges):
    """All 2^4 per-shard exchange assignments x every kernel config, on a
    structure with both a dense band and scattered rows."""
    A = mixed_structure(256, 256 * 6, seed=0)
    uniform = len(set(exchanges)) == 1
    for kernel, sk in _KERNEL_CONFIGS:
        plan = SpmvPlan(num_shards=4, kernel=kernel, shard_kernels=sk,
                        exchange=exchanges[0],
                        shard_exchanges=None if uniform else exchanges)
        _check_plan(A, plan, batch=(kernel in ("seg", "tile")))


@pytest.mark.parametrize("layout", ["block", "cyclic"])
@pytest.mark.parametrize("distribution", ["row", "nonzero"])
def test_mixed_exchange_all_layouts_distributions(layout, distribution):
    A = mixed_structure(256, 256 * 6, seed=1)
    plan = SpmvPlan(num_shards=4, layout=layout, distribution=distribution,
                    kernel="seg", exchange="halo",
                    shard_exchanges=("halo", "allgather", "allgather",
                                     "halo"))
    _check_plan(A, plan)


@pytest.mark.parametrize("kernel", PLAN_KERNELS)
def test_degenerate_zero_nnz_shards_all_exchange_mixes(kernel):
    """6x6 matrix over 4 shards: at least two shards lower to zero stored
    entries; every exchange mix must still reproduce the oracle."""
    A = csr_from_coo([0, 0, 5], [1, 4, 0], [2.0, -1.0, 3.0], (6, 6))
    for exchanges in [("halo",) * 4, ("allgather",) * 4,
                      ("halo", "allgather", "halo", "allgather")]:
        plan = SpmvPlan(num_shards=4, kernel=kernel,
                        exchange=exchanges[0],
                        shard_exchanges=None if len(set(exchanges)) == 1
                        else exchanges)
        _check_plan(A, plan)


@pytest.mark.parametrize("kernel", PLAN_KERNELS)
@pytest.mark.parametrize("exchange", PLAN_EXCHANGES)
def test_single_shard_mesh(kernel, exchange):
    """num_shards=1: no remote reads exist, every policy must degenerate
    to the same local product."""
    A = mixed_structure(128, 128 * 5, seed=2)
    plan = SpmvPlan(num_shards=1, kernel=kernel, exchange=exchange,
                    shard_exchanges=(exchange,))
    _check_plan(A, plan)


@pytest.mark.parametrize("kernel", PLAN_KERNELS)
@pytest.mark.parametrize("exchange", PLAN_EXCHANGES)
def test_single_shard_device_mesh(kernel, exchange):
    """The same single-shard programs through the device executor on a
    1-device mesh (Pallas kernels, interpreted on the CPU), against the
    float64 oracle."""
    import jax
    from jax.sharding import AxisType
    mesh = jax.make_mesh((1,), ("model",), axis_types=(AxisType.Auto,),
                         devices=jax.devices()[:1])
    A = mixed_structure(128, 128 * 5, seed=2)
    prog = lower(A, SpmvPlan(num_shards=1, kernel=kernel, exchange=exchange,
                             shard_exchanges=(exchange,)))
    x = np.random.default_rng(7).standard_normal(A.ncols)
    y = execute(prog, x, backend="shard_map", mesh=mesh, use_kernel=True)
    np.testing.assert_allclose(y, csr_matvec(A, x), atol=2e-4, rtol=2e-4)


def _property(M, density, num_shards, layout, distribution, kid, seed,
              exchanges):
    rng = np.random.default_rng(seed)
    A = _random_csr(rng, M, density)
    kernel, sk = _KERNEL_CONFIGS[kid % len(_KERNEL_CONFIGS)]
    if sk is not None and num_shards != 4:
        sk = tuple(sk[i % len(sk)] for i in range(num_shards))
    ex = tuple(exchanges[i % len(exchanges)] for i in range(num_shards))
    plan = SpmvPlan(num_shards=num_shards, layout=layout,
                    distribution=distribution, kernel=kernel,
                    shard_kernels=sk, exchange=ex[0],
                    shard_exchanges=None if len(set(ex)) == 1 else ex)
    _check_plan(A, plan)


if HAVE_HYPOTHESIS:

    @settings(max_examples=30, deadline=None,
              suppress_health_check=[HealthCheck.too_slow])
    @given(M=hst.integers(min_value=8, max_value=160),
           density=hst.floats(min_value=0.002, max_value=0.2),
           num_shards=hst.sampled_from([1, 2, 4]),
           layout=hst.sampled_from(["block", "cyclic"]),
           distribution=hst.sampled_from(["row", "nonzero"]),
           kid=hst.integers(min_value=0, max_value=len(_KERNEL_CONFIGS) - 1),
           seed=hst.integers(min_value=0, max_value=2**31 - 1),
           exchanges=hst.lists(hst.sampled_from(PLAN_EXCHANGES),
                               min_size=4, max_size=4))
    def test_property_exchange_kernel_grid(M, density, num_shards, layout,
                                           distribution, kid, seed,
                                           exchanges):
        _property(M, density, num_shards, layout, distribution, kid, seed,
                  tuple(exchanges))

else:

    @pytest.mark.parametrize("seed", range(20))
    def test_property_exchange_kernel_grid_fallback(seed):
        """Deterministic stand-in for the hypothesis sweep (hypothesis is
        absent in the pinned local environment): the same property over a
        seeded random draw of every axis."""
        rng = np.random.default_rng(1000 + seed)
        M = int(rng.integers(8, 161))
        density = float(rng.uniform(0.002, 0.2))
        num_shards = int(rng.choice([1, 2, 4]))
        layout = str(rng.choice(["block", "cyclic"]))
        distribution = str(rng.choice(["row", "nonzero"]))
        kid = int(rng.integers(0, len(_KERNEL_CONFIGS)))
        exchanges = tuple(rng.choice(PLAN_EXCHANGES, size=4))
        _property(M, density, num_shards, layout, distribution, kid,
                  int(rng.integers(0, 2**31)), exchanges)
