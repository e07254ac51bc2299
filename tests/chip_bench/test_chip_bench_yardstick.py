"""The benchmark's yardstick on the CPU: generators, reference, control,
traffic schedule, percentile, window closing, peaks and byte floor."""
import json
import math
import time

import numpy as np
import pytest

from chip_bench import matrix, reference, roofline, traffic
from repro.core.sparse_matrix import CSRMatrix, csr_matvec
from repro.data import matrices

ARROW = {"pattern": "arrow_fem", "rows": 3000, "nnz": 65000,
         "structure_seed": 3,
         "pattern_params": {"hot_frac": 0.125, "dense_boost": 3.7}}
BAND = {"pattern": "banded", "rows": 9430, "nnz": 776000,
        "structure_seed": 5,
        "pattern_params": {"bandwidth": 94, "scatter_frac": 0.12}}


@pytest.mark.parametrize("config, original", [
    (ARROW, lambda: matrices.arrow_fem(3000, 65000, seed=3)),
    (BAND, lambda: matrices.banded(9430, 776000, 94, seed=5)),
], ids=["arrow_fem", "banded"])
def test_copied_generators_equal_the_programs(config, original):
    mine, theirs = matrix.build_matrix(config, 2 ** 33 + 1), original()
    assert mine.shape == theirs.shape
    np.testing.assert_array_equal(mine.row_ptr, theirs.row_ptr)
    np.testing.assert_array_equal(mine.col_index, theirs.col_index)


@pytest.mark.parametrize("config", [ARROW, BAND], ids=["arrow", "band"])
def test_values_come_from_the_seed_and_the_pattern_does_not(config):
    a = matrix.build_matrix(config, 2 ** 33 + 1)
    b = matrix.build_matrix(config, 2 ** 33 + 1)
    c = matrix.build_matrix(config, 7)
    d = matrix.build_matrix(config, 2 ** 33 + 1, index=1)
    for m in (b, c, d):
        np.testing.assert_array_equal(m.row_ptr, a.row_ptr)
        np.testing.assert_array_equal(m.col_index, a.col_index)
    np.testing.assert_array_equal(a.values, b.values)
    assert not np.array_equal(a.values, c.values)
    assert not np.array_equal(a.values, d.values)


def _small(seed=0):
    A = matrix.build_matrix(ARROW, seed)
    x = np.random.default_rng(seed).standard_normal(A.ncols)
    return A, x


def test_reference_equals_csr_matvec():
    A, x = _small()
    theirs = CSRMatrix(shape=A.shape, values=A.values,
                       col_index=A.col_index, row_ptr=A.row_ptr)
    y, scale = reference.Reference(A).answer(x)
    np.testing.assert_allclose(y, csr_matvec(theirs, x), rtol=1e-12,
                               atol=1e-12)
    absA = CSRMatrix(shape=A.shape, values=np.abs(A.values),
                     col_index=A.col_index, row_ptr=A.row_ptr)
    np.testing.assert_allclose(scale, csr_matvec(absA, np.abs(x)),
                               rtol=1e-12)
    X = np.stack([x, -2 * x], axis=1)
    Y, _ = reference.Reference(A).answer(X)
    np.testing.assert_allclose(Y, csr_matvec(theirs, X), rtol=1e-12,
                               atol=1e-12)


def test_norm_err_flags_shape_nonfinite_and_offsets():
    A, x = _small()
    ref = reference.Reference(A)
    y, s = ref.answer(x)
    assert ref.norm_err(y, y, s) == 0.0
    assert ref.norm_err(y[:-1], y, s) == math.inf
    bad = y.copy()
    bad[5] = np.nan
    assert ref.norm_err(bad, y, s) == math.inf
    bad = y.copy()
    bad[5] += s[5] * 1e-3
    assert ref.norm_err(bad, y, s) == pytest.approx(1e-3)


@pytest.mark.parametrize("config", [ARROW, BAND], ids=["arrow", "band"])
def test_float32_passes_the_limit_and_the_bf16_control_fails_it(config):
    """The limit 1e-4 lies between what float32 serving reads and what
    the control (values and x in bfloat16) reads, at a small size."""
    import jax
    import jax.numpy as jnp

    A = matrix.build_matrix(config, 11)
    ref = reference.Reference(A)
    xs = traffic.request_pool(A.ncols, 1, 4, 11)
    ctrl = reference.bf16_control(A, xs, jax.devices()[0])
    rows = jnp.asarray(A.row_ids())
    for x, yc in zip(xs, ctrl):
        y_ref, scale = ref.answer(x)
        prod = jnp.asarray(A.values, jnp.float32) * jnp.asarray(x)[
            jnp.asarray(A.col_index)]
        y32 = jax.ops.segment_sum(prod, rows, num_segments=A.nrows)
        assert ref.norm_err(np.asarray(y32), y_ref, scale) < 1e-5
        assert ref.norm_err(yc, y_ref, scale) > 1e-3


def _open(rate, arrival_seed):
    return {"loop": "open", "schedule": "poisson", "rate_per_s": rate,
            "arrival_seed": arrival_seed, "threads": 8, "batch": 1}


def test_open_loop_schedule_keeps_count_and_gaps_across_seeds():
    a = traffic.due_times(_open(18.0, 2 ** 33 + 3), 20.0)
    b = traffic.due_times(_open(18.0, 5), 20.0)
    assert len(a) == len(b) == 360
    assert a[0] == b[0] == 0.0
    assert (np.diff(a) > 0).all() and a[-1] < 20.0
    gaps_of = lambda due: np.sort(np.append(np.diff(due), 20.0 - due[-1]))
    np.testing.assert_allclose(gaps_of(a), gaps_of(b))
    assert not np.allclose(np.diff(a), np.diff(b))
    np.testing.assert_array_equal(
        a, traffic.due_times(_open(18.0, 2 ** 33 + 3), 20.0))
    gaps = np.diff(a)
    assert 0.8 < gaps.std() / gaps.mean() < 1.2      # exponential-like


def test_a_schedule_is_found_by_the_name_the_mix_gives(tmp_path,
                                                      monkeypatch):
    """A new schedule is a new module of ``schedules/``, found by name."""
    import sys
    pkg = tmp_path / "chip_bench" / "schedules"
    pkg.mkdir(parents=True)
    (pkg / "every_tenth.py").write_text(
        "import numpy as np\n"
        "def due_times(mix, seconds):\n"
        "    return np.arange(0.0, seconds, 0.1)\n")
    import chip_bench.schedules as schedules
    monkeypatch.setattr(schedules, "__path__",
                        list(schedules.__path__) + [str(pkg)])
    monkeypatch.delitem(sys.modules, "chip_bench.schedules.every_tenth",
                        raising=False)
    due = traffic.due_times({"schedule": "every_tenth"}, 1.0)
    np.testing.assert_allclose(due, np.arange(10) / 10)


def test_tenants_share_requests_by_the_mixs_zipf_law_alone():
    mix = {"tenant_zipf": 1.0, "arrival_seed": 3}
    seq = traffic.tenant_sequence(mix, 3, 60000)
    share = np.bincount(seq, minlength=3) / seq.size
    np.testing.assert_allclose(share, np.array([1, 1 / 2, 1 / 3]) / (
        1 + 1 / 2 + 1 / 3), atol=0.01)
    np.testing.assert_array_equal(seq, traffic.tenant_sequence(mix, 3,
                                                                60000))
    even = traffic.tenant_sequence({}, 2, 60000)
    assert abs(even.mean() - 0.5) < 0.01
    assert (traffic.tenant_sequence(mix, 1, 5) == 0).all()


def test_the_run_seed_moves_the_vectors_and_not_the_arrivals():
    mix = dict(_open(40.0, 5), threads=4)
    a = traffic.drive(mix, lambda r: r.pool, 0.3, 2 ** 33 + 1, 64, 2)
    b = traffic.drive(mix, lambda r: r.pool, 0.3, 7, 64, 2)
    assert [r.due for r in a.requests] == [r.due for r in b.requests]
    assert [r.tenant for r in a.requests] == [r.tenant for r in b.requests]
    assert {r.tenant for r in a.requests} == {0, 1}
    assert [r.pool for r in a.requests] != [r.pool for r in b.requests]


def test_percentile_is_nearest_rank():
    sample = np.arange(1, 101, dtype=float)
    assert traffic.percentile(sample, 95) == 95.0
    assert traffic.percentile(sample[::-1], 50) == 50.0
    assert traffic.percentile([3.0], 95) == 3.0
    with_failures = list(range(1, 95)) + [math.inf] * 6
    assert traffic.percentile(with_failures, 95) == math.inf


def _slow_call(seconds):
    def call(req):
        time.sleep(seconds)
        return req.pool
    return call


def test_closed_window_closes_on_whole_requests():
    w = traffic.drive({"loop": "closed", "clients": 1, "batch": 1},
                      _slow_call(0.2), 0.5, 1, 4)
    assert w.attempted == 3 and w.failed == 0
    assert all(r.sent < 0.5 for r in w.requests)
    assert w.close >= 0.6 and w.close == max(r.done for r in w.requests)
    assert [r.answer for r in w.requests] == [r.pool for r in w.requests]


def test_open_window_waits_for_every_due_request():
    w = traffic.drive(_open(20.0, 0), _slow_call(0.3), 0.5, 2, 4)
    assert w.attempted == 10 and w.failed == 0
    assert all(r.due < 0.5 for r in w.requests)
    assert w.close >= max(r.due for r in w.requests) + 0.3


def test_a_failed_request_is_counted():
    def call(req):
        if req.pool == 0:
            raise RuntimeError("boom")
        return req.pool
    w = traffic.drive({"loop": "closed", "clients": 1, "batch": 1}, call,
                      0.05, 3, 2)
    assert w.failed >= 1 and w.attempted > w.failed
    assert any("boom" in (r.error or "") for r in w.requests)


def test_unknown_device_kind_raises():
    assert roofline.peak("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(roofline.UnknownDevice, match="TPU v9"):
        roofline.peak("TPU v9")


def test_the_peaks_name_their_source_and_nothing_unread():
    for entry in json.loads(roofline.PEAKS_FILE.read_text()).values():
        assert set(entry) == {"hbm_bytes_per_s", "source"}


def test_floor_bytes_count_values_and_vectors_only():
    assert roofline.floor_bytes(100, 10, 20, 1) == 400 + 120
    assert roofline.floor_bytes(100, 10, 20, 8) == 400 + 8 * 120
