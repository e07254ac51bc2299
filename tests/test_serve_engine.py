"""Serving-path regression tests: Engine.generate edge semantics, the
SparseMatrixEngine error/stats contract, batched multi-RHS SpMV exactness,
the feature-keyed plan cache (in-memory and disk-backed), warm-start
ingest from persistent program artifacts, per-tenant rebalance state, and
cross-request micro-batching.
"""
import numpy as np
import pytest

from repro.core.sparse_matrix import csr_to_dense
from repro.core.spmv import SpmvPlan, build_distributed, local_spmv
from repro.data.matrices import make_matrix
from repro.serve.engine import Engine, ServeConfig, SparseMatrixEngine


# --------------------------------------------------------------------------
# Engine.generate edges (prefill/decode semantics)
# --------------------------------------------------------------------------

@pytest.fixture(scope="module")
def lm_engine():
    import jax
    from repro.configs.registry import get_smoke_config
    from repro.models import params as pp
    cfg = get_smoke_config("qwen3_4b")
    params = pp.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def test_generate_steps_zero_returns_prompts(lm_engine):
    cfg, params = lm_engine
    eng = Engine(cfg, params, ServeConfig(max_len=32))
    prompts = np.array([[1, 2, 3], [4, 5, 6]], dtype=np.int32)
    out = eng.generate(prompts, steps=0)
    np.testing.assert_array_equal(out, prompts)
    # and a (B, 0) prompt with steps=0 is a harmless no-op
    empty = np.zeros((2, 0), dtype=np.int32)
    assert eng.generate(empty, steps=0).shape == (2, 0)
    # steps=0 never samples, so it must not demand a key either
    sampling = Engine(cfg, params, ServeConfig(max_len=32, temperature=0.9))
    np.testing.assert_array_equal(sampling.generate(prompts, steps=0),
                                  prompts)


def test_generate_empty_prefill_raises(lm_engine):
    """S0 == 0 with steps > 0 used to crash with NameError on `logits`;
    the chosen semantics are an explicit error telling callers to seed
    the prompt (e.g. BOS)."""
    cfg, params = lm_engine
    eng = Engine(cfg, params, ServeConfig(max_len=32))
    empty = np.zeros((2, 0), dtype=np.int32)
    with pytest.raises(ValueError, match="empty prompt"):
        eng.generate(empty, steps=4)


def test_generate_temperature_requires_key(lm_engine):
    """temperature > 0 without a key used to silently decode greedily."""
    import jax
    cfg, params = lm_engine
    eng = Engine(cfg, params, ServeConfig(max_len=32, temperature=0.8))
    prompts = np.array([[1, 2]], dtype=np.int32)
    with pytest.raises(ValueError, match="PRNG key"):
        eng.generate(prompts, steps=2)
    out = eng.generate(prompts, steps=2, key=jax.random.PRNGKey(0))
    assert out.shape == (1, 4)


def test_generate_greedy_still_works(lm_engine):
    cfg, params = lm_engine
    eng = Engine(cfg, params, ServeConfig(max_len=32))
    prompts = np.array([[1, 2]], dtype=np.int32)
    out = eng.generate(prompts, steps=3)
    assert out.shape == (1, 5)
    np.testing.assert_array_equal(out[:, :2], prompts)


# --------------------------------------------------------------------------
# SparseMatrixEngine contract
# --------------------------------------------------------------------------

def test_spmv_unknown_name_is_actionable_and_uncounted():
    eng = SparseMatrixEngine(num_shards=4)
    A = make_matrix("ford1", scale=0.05)
    eng.ingest("ford", A)
    x = np.zeros(A.ncols)
    with pytest.raises(KeyError, match="ford"):
        eng.spmv("typo", x)
    # the failed call neither counted nor created anything
    assert eng.stats()["ford"]["spmv_count"] == 0
    assert set(eng.stats()) == {"ford"}
    eng.spmv("ford", x)
    assert eng.stats()["ford"]["spmv_count"] == 1
    with pytest.raises(KeyError):
        eng.plan("typo")


def test_batched_spmv_bitwise_matches_per_vector():
    """(M, B) blocks equal per-vector calls bitwise, both kernels."""
    A = make_matrix("cop20k_A", scale=0.005)
    rng = np.random.default_rng(0)
    X = rng.standard_normal((A.ncols, 4))
    for kernel in ("ell", "seg"):
        dist = build_distributed(A, SpmvPlan(kernel=kernel, num_shards=4,
                                             reordering="bfs"))
        Y = local_spmv(dist, X)
        assert Y.shape == (A.nrows, 4)
        for b in range(X.shape[1]):
            assert np.array_equal(Y[:, b], local_spmv(dist, X[:, b])), \
                (kernel, b)
        np.testing.assert_allclose(Y, csr_to_dense(A) @ X, atol=1e-6)
    with pytest.raises(ValueError, match="elements"):
        local_spmv(dist, X[: A.ncols // 2])
    with pytest.raises(ValueError, match=r"\(N,\) or \(N, B\)"):
        local_spmv(dist, X[..., None])


def test_engine_serves_batched_requests():
    eng = SparseMatrixEngine(num_shards=4)
    A = make_matrix("rmat", scale=0.002)
    eng.ingest("r", A)
    X = np.random.default_rng(1).standard_normal((A.ncols, 3))
    Y = eng.spmv("r", X)
    np.testing.assert_allclose(Y, csr_to_dense(A) @ X, atol=1e-6)
    for b in range(3):
        assert np.array_equal(eng.spmv("r", X[:, b]), Y[:, b])


def test_plan_cache_reuses_structural_twins():
    eng = SparseMatrixEngine(num_shards=4)
    c1 = eng.ingest("m1", make_matrix("rmat", scale=0.002, seed=0))
    assert eng.plan_cache_hits == 0
    c2 = eng.ingest("m2", make_matrix("rmat", scale=0.002, seed=7))
    assert eng.plan_cache_hits == 1
    assert eng.stats()["m2"]["plan_cache_hit"]
    assert not eng.stats()["m1"]["plan_cache_hit"]
    assert c2.plan == c1.plan
    assert len(c2.ranking) == 1 and c2.probed == 0   # no grid re-run
    # a different archetype misses
    eng.ingest("banded", make_matrix("ford1", scale=0.05))
    assert eng.plan_cache_hits == 1
    # cached plans still serve correctly
    A2 = make_matrix("rmat", scale=0.002, seed=7)
    x = np.random.default_rng(2).standard_normal(A2.ncols)
    np.testing.assert_allclose(eng.spmv("m2", x), csr_to_dense(A2) @ x,
                               atol=1e-6)


def test_plan_cache_can_be_disabled():
    eng = SparseMatrixEngine(num_shards=4, plan_cache=False)
    eng.ingest("m1", make_matrix("rmat", scale=0.002, seed=0))
    c2 = eng.ingest("m2", make_matrix("rmat", scale=0.002, seed=7))
    assert eng.plan_cache_hits == 0
    assert len(c2.ranking) > 1                       # full grid ran


# --------------------------------------------------------------------------
# Multi-tenant router: warm-start artifacts, shared plan cache, batching
# --------------------------------------------------------------------------

def test_warm_start_ingest_skips_autotune_and_lower(tmp_path, monkeypatch):
    """A restarted engine pointed at the artifact store loads every tenant
    digest-hit: no autotune, no lower, bitwise-identical serving."""
    A = make_matrix("cop20k_A", scale=0.005)
    B = make_matrix("ford1", scale=0.05)
    store = str(tmp_path / "artifacts")
    e1 = SparseMatrixEngine(num_shards=4, artifact_dir=store)
    c1a = e1.ingest("a", A)
    e1.ingest("b", B)
    rng = np.random.default_rng(0)
    xa = rng.standard_normal(A.ncols)
    xb = rng.standard_normal(B.ncols)
    ya, yb = e1.spmv("a", xa), e1.spmv("b", xb)

    # the warm path must touch neither the autotuner nor the lowerer
    import repro.serve.router as router
    monkeypatch.setattr(router, "autotune", _boom)
    monkeypatch.setattr(router, "lower", _boom)
    e2 = SparseMatrixEngine(num_shards=4, artifact_dir=store)
    c2a = e2.ingest("a", A)
    e2.ingest("b", B)
    assert e2.warm_starts == 2
    assert e2.stats()["a"]["warm_start"] and e2.stats()["b"]["warm_start"]
    assert c2a == c1a                       # full PlanChoice round-trips
    assert np.array_equal(e2.spmv("a", xa), ya)
    assert np.array_equal(e2.spmv("b", xb), yb)


def _boom(*a, **k):
    raise AssertionError("warm-start ingest must not reach this path")


def test_warm_start_digest_mismatch_falls_back_cold(tmp_path):
    """Re-ingesting a same-name tenant with different values must miss the
    artifact (stale numerics) and re-tune cold — correctly."""
    from repro.core.sparse_matrix import CSRMatrix
    A = make_matrix("rmat", scale=0.002)
    store = str(tmp_path / "artifacts")
    e1 = SparseMatrixEngine(num_shards=4, artifact_dir=store)
    e1.ingest("a", A)
    A2 = CSRMatrix(shape=A.shape, values=A.values * 2.0,
                   col_index=A.col_index, row_ptr=A.row_ptr)
    e2 = SparseMatrixEngine(num_shards=4, artifact_dir=store)
    e2.ingest("a", A2)
    assert not e2.stats()["a"]["warm_start"]
    x = np.random.default_rng(1).standard_normal(A.ncols)
    np.testing.assert_allclose(e2.spmv("a", x), csr_to_dense(A2) @ x,
                               atol=1e-6)
    # the fallback also rewrote the bundle: a third engine warm-starts A2
    e3 = SparseMatrixEngine(num_shards=4, artifact_dir=store)
    e3.ingest("a", A2)
    assert e3.stats()["a"]["warm_start"]
    assert np.array_equal(e3.spmv("a", x), e2.spmv("a", x))


def test_disk_plan_cache_shared_across_engine_instances(tmp_path):
    """plan_cache_dir makes the feature-keyed cache an L2 shared by
    engine instances: the second instance skips the grid entirely."""
    cache = str(tmp_path / "plans")
    e1 = SparseMatrixEngine(num_shards=4, plan_cache_dir=cache)
    c1 = e1.ingest("m1", make_matrix("rmat", scale=0.002, seed=0))
    assert e1.plan_cache_hits == 0
    e2 = SparseMatrixEngine(num_shards=4, plan_cache_dir=cache)
    c2 = e2.ingest("m2", make_matrix("rmat", scale=0.002, seed=7))
    assert e2.plan_cache_hits == 1
    assert c2.plan == c1.plan
    assert len(c2.ranking) == 1 and c2.probed == 0   # no grid re-run


def test_per_tenant_rebalance_config_override():
    from repro.serve.rebalance import RebalanceConfig
    eng = SparseMatrixEngine(num_shards=4)           # no engine default
    A = make_matrix("rmat", scale=0.002)
    eng.ingest("watched", A, rebalance=RebalanceConfig(window=16))
    eng.ingest("plain", A)
    assert "rebalance" in eng.stats()["watched"]
    assert "rebalance" not in eng.stats()["plain"]
    # and an engine-wide default can be switched off per tenant
    eng2 = SparseMatrixEngine(num_shards=4, rebalance=True)
    eng2.ingest("off", A, rebalance=False)
    eng2.ingest("on", A)
    assert "rebalance" not in eng2.stats()["off"]
    assert "rebalance" in eng2.stats()["on"]


def test_micro_batching_gathers_concurrent_requests():
    """Concurrent single-vector requests for one tenant share a batched
    (N, B) execute and still return bitwise-solo results."""
    import threading
    from repro.serve.router import MicroBatchConfig
    A = make_matrix("cop20k_A", scale=0.005)
    solo = SparseMatrixEngine(num_shards=4)
    solo.ingest("a", A)
    eng = SparseMatrixEngine(
        num_shards=4,
        micro_batch=MicroBatchConfig(max_batch=4, max_wait_ms=100.0))
    eng.ingest("a", A)
    rng = np.random.default_rng(2)
    xs = [rng.standard_normal(A.ncols) for _ in range(4)]
    want = [solo.spmv("a", x) for x in xs]
    got = [None] * 4
    barrier = threading.Barrier(4)

    def hit(i):
        barrier.wait()
        got[i] = eng.spmv("a", xs[i])

    threads = [threading.Thread(target=hit, args=(i,)) for i in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    for i in range(4):
        assert np.array_equal(got[i], want[i]), i
    mb = eng.stats()["a"]["micro_batch"]
    assert mb["requests"] == 4
    assert mb["widest"] >= 2                 # at least one real gather
    assert eng.stats()["a"]["spmv_count"] == 4
    # multi-RHS blocks bypass the batcher unchanged
    X = np.stack(xs, axis=1)
    assert np.array_equal(eng.spmv("a", X), np.stack(want, axis=1))


def test_rebalance_swap_rewrites_artifact(tmp_path):
    """After a drift-triggered swap the tenant's bundle holds the *new*
    program: a restart warm-starts straight into the post-drift plan."""
    from repro.serve.rebalance import RebalanceConfig
    cfg = RebalanceConfig(window=32, patience=2, cooldown=2, probe=2)
    A = make_matrix("cop20k_A", scale=0.005)
    N = A.ncols
    store = str(tmp_path / "artifacts")
    eng = SparseMatrixEngine(num_shards=4, rebalance=cfg,
                             artifact_dir=store)
    eng.ingest("a", A)
    m = eng._matrices["a"]
    d = m.dist
    order = np.arange(N) if d.perm is None else d.perm
    hot = np.flatnonzero(d.x_layout.owner_of(order) == 0)
    rng = np.random.default_rng(0)
    k = max(N // 20, 8)
    for _ in range(2 * cfg.window):                  # uniform warm-up
        x = np.zeros(N)
        x[rng.integers(0, N, k)] = rng.standard_normal(k)
        eng.spmv("a", x)
    for i in range(10 * cfg.window):                 # sustained hot-spot
        x = np.zeros(N)
        x[rng.choice(hot, size=k)] = rng.standard_normal(k)
        eng.spmv("a", x)
        if any(e.swapped for e in m.rebalance_log):
            break
    assert any(e.swapped for e in m.rebalance_log), "drift never swapped"
    # restart: the bundle must hand back the swapped-in plan, warm
    fresh = SparseMatrixEngine(num_shards=4, artifact_dir=store)
    fresh.ingest("a", A)
    assert fresh.stats()["a"]["warm_start"]
    assert fresh.plan("a") == eng.plan("a")
    x = np.zeros(N)
    x[rng.choice(hot, size=k)] = rng.standard_normal(k)
    assert np.array_equal(fresh.spmv("a", x), eng.spmv("a", x))


# --------------------------------------------------------------------------
# Serving on the device executor (a 1-device CPU mesh, interpret mode)
# --------------------------------------------------------------------------

def _mesh1():
    import jax
    from jax.sharding import AxisType
    return jax.make_mesh((1,), ("model",), axis_types=(AxisType.Auto,),
                         devices=jax.devices()[:1])


def _assert_within_smoke_tol(A, X, Y):
    """|y - y_ref|_i <= 1e-4 * (|A| |x|)_i, the chip smoke's bound."""
    import dataclasses
    from repro.core.sparse_matrix import csr_matvec
    absA = dataclasses.replace(A, values=np.abs(A.values))
    err = np.abs(np.asarray(Y, np.float64) - csr_matvec(A, X))
    assert (err <= 1e-4 * csr_matvec(absA, np.abs(X))).all()


@pytest.mark.parametrize("name,scale", [("cop20k_A", 0.005),
                                        ("ford1", 0.05), ("rmat", 0.002)])
def test_device_engine_serves_within_tolerance(name, scale):
    """Ingest on a mesh builds the device function once; 1-D and (N, B)
    requests go through it and match the float64 oracle."""
    A = make_matrix(name, scale=scale)
    eng = SparseMatrixEngine(mesh=_mesh1())
    assert eng.num_shards == 1
    eng.ingest("a", A)
    fn = eng.device_fn("a")
    assert fn is not None and fn.program is eng._matrices["a"].dist
    rng = np.random.default_rng(3)
    x = rng.standard_normal(A.ncols)
    y = eng.spmv("a", x)
    assert y.shape == (A.nrows,) and y.dtype == np.float32
    _assert_within_smoke_tol(A, x, y)
    X = rng.standard_normal((A.ncols, 4))
    Y = eng.spmv("a", X)
    assert Y.shape == (A.nrows, 4)
    _assert_within_smoke_tol(A, X, Y)
    assert eng.stats()["a"]["device_operand_bytes"] == fn.operand_bytes > 0
    assert eng.stats()["a"]["device_passes"] == fn.passes == 1  # one shard
    assert eng.device_fn("a") is fn          # built once, not per request


def test_device_engine_micro_batched_requests():
    import threading
    from repro.serve.router import MicroBatchConfig
    A = make_matrix("cop20k_A", scale=0.005)
    eng = SparseMatrixEngine(
        mesh=_mesh1(),
        micro_batch=MicroBatchConfig(max_batch=4, max_wait_ms=100.0))
    eng.ingest("a", A)
    rng = np.random.default_rng(4)
    xs = [rng.standard_normal(A.ncols) for _ in range(4)]
    got = [None] * 4
    barrier = threading.Barrier(4)

    def hit(i):
        barrier.wait()
        got[i] = eng.spmv("a", xs[i])

    threads = [threading.Thread(target=hit, args=(i,)) for i in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    for x, y in zip(xs, got):
        _assert_within_smoke_tol(A, x, y)
    assert eng.stats()["a"]["micro_batch"]["requests"] == 4
    assert eng.stats()["a"]["spmv_count"] == 4


def test_device_engine_warm_start_builds_device_fn(tmp_path, monkeypatch):
    """A warm start from an artifact skips autotune and lower but still
    builds the tenant's device function."""
    A = make_matrix("cop20k_A", scale=0.005)
    store = str(tmp_path / "artifacts")
    e1 = SparseMatrixEngine(mesh=_mesh1(), artifact_dir=store)
    e1.ingest("a", A)
    x = np.random.default_rng(5).standard_normal(A.ncols)
    y1 = e1.spmv("a", x)
    import repro.serve.router as router
    monkeypatch.setattr(router, "autotune", _boom)
    monkeypatch.setattr(router, "lower", _boom)
    e2 = SparseMatrixEngine(mesh=_mesh1(), artifact_dir=store)
    e2.ingest("a", A)
    assert e2.stats()["a"]["warm_start"]
    fn = e2.device_fn("a")
    assert fn is not None and fn.program is e2._matrices["a"].dist
    y2 = e2.spmv("a", x)
    _assert_within_smoke_tol(A, x, y2)
    assert np.array_equal(y1, y2)


def test_device_engine_mesh_and_shard_count_mismatch_raise():
    import jax
    from jax.sharding import AxisType
    with pytest.raises(ValueError, match="disagrees"):
        SparseMatrixEngine(mesh=_mesh1(), num_shards=4)
    two_d = jax.make_mesh((1, 1), ("data", "model"),
                          axis_types=(AxisType.Auto,) * 2,
                          devices=jax.devices()[:1])
    with pytest.raises(ValueError, match="1-D mesh"):
        SparseMatrixEngine(mesh=two_d)
    assert SparseMatrixEngine(mesh=_mesh1(), num_shards=1).num_shards == 1
    assert SparseMatrixEngine().num_shards == 8      # no mesh: the default
