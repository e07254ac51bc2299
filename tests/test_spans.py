"""The program's spans and counters: ``repro.core.spans.span`` itself, the
engine's ingest phases, served-block stages and micro-batch queue wait,
and the named scopes of the device step."""
import sys
import threading
import time

import numpy as np
import pytest

from repro.core.spans import span
from repro.data.matrices import make_matrix


def test_span_sums_seconds_and_counts_calls_into_its_dict():
    into: dict = {}
    with span("outer", into):
        for _ in range(3):
            with span("inner", into):
                time.sleep(0.002)
    assert into["inner#"] == 3 and into["outer#"] == 1
    assert into["inner"] >= 0.006
    assert into["outer"] >= into["inner"]
    with span("not_counted"):
        pass
    assert set(into) == {"outer", "outer#", "inner", "inner#"}


def test_span_counts_a_body_that_raises():
    into: dict = {}
    with pytest.raises(KeyError):
        with span("fails", into):
            raise KeyError("x")
    assert into["fails#"] == 1


def test_spans_counted_from_many_threads_lose_no_update():
    """32 threads, more than the cores, count into one dict under its lock
    with the interpreter switching threads as often as it can."""
    into, lock = {}, threading.Lock()
    n_threads, n_spans = 32, 200

    def work():
        for _ in range(n_spans):
            with span("s", into, lock):
                pass
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=work) for _ in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in threads)
    assert into["s#"] == n_threads * n_spans


def test_a_span_and_its_ids_are_in_a_profiler_trace(tmp_path):
    import jax
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(tmp_path))
    with span("spmv.request", tenant="a", req=3):
        with span("spmv.scatter_x", {}):
            pass
    jax.profiler.stop_trace()
    pb = sorted(tmp_path.rglob("*.xplane.pb"))[-1]
    found = {ev.name: (plane.name, dict(ev.stats), ev.start_ns,
                       ev.duration_ns)
             for plane in ProfileData.from_file(str(pb)).planes
             for line in plane.lines for ev in line.events
             if ev.name.startswith("spmv.")}
    plane, stats, start, dur = found["spmv.request"]
    assert plane == "/host:CPU" and stats == {"tenant": "a", "req": 3}
    _, _, inner_start, inner_dur = found["spmv.scatter_x"]
    assert start <= inner_start and inner_start + inner_dur <= start + dur


def _mesh1():
    import jax
    from jax.sharding import AxisType
    return jax.make_mesh((1,), ("model",), axis_types=(AxisType.Auto,),
                         devices=jax.devices()[:1])


STAGES = ("spmv.scatter_x", "spmv.put", "spmv.wait", "spmv.gather_b")
PHASES = ("ingest.plan", "ingest.lower", "ingest.stack", "ingest.place")


def test_the_engine_counts_its_ingest_phases_and_block_stages():
    from repro.serve.engine import SparseMatrixEngine

    A = make_matrix("cop20k_A", scale=0.005)
    eng = SparseMatrixEngine(mesh=_mesh1())
    t = time.perf_counter()
    eng.ingest("a", A)
    ingest_s = time.perf_counter() - t
    phases = eng.stats()["a"]["ingest_phases_s"]
    assert set(phases) == set(PHASES) | {p + "#" for p in PHASES}
    assert all(phases[p + "#"] == 1 and phases[p] > 0 for p in PHASES)
    assert sum(phases[p] for p in PHASES) <= ingest_s
    rng = np.random.default_rng(0)
    n = 5
    for i in range(n):
        eng.spmv("a", rng.standard_normal(A.ncols) if i % 2
                 else rng.standard_normal((A.ncols, 3)))
    stages = eng.stats()["a"]["stages_s"]
    assert set(stages) == set(STAGES) | {s + "#" for s in STAGES}
    assert all(stages[s + "#"] == n and stages[s] > 0 for s in STAGES)


def test_a_warm_start_counts_its_load_as_the_plan_phase(tmp_path):
    from repro.serve.engine import SparseMatrixEngine

    A = make_matrix("cop20k_A", scale=0.005)
    store = str(tmp_path / "artifacts")
    SparseMatrixEngine(mesh=_mesh1(), artifact_dir=store).ingest("a", A)
    eng = SparseMatrixEngine(mesh=_mesh1(), artifact_dir=store)
    eng.ingest("a", A)
    phases = eng.stats()["a"]["ingest_phases_s"]
    assert eng.stats()["a"]["warm_start"]
    assert phases["ingest.plan#"] == 1 and "ingest.lower" not in phases
    assert phases["ingest.stack#"] == phases["ingest.place#"] == 1


def test_the_micro_batch_queue_wait_counts_every_request():
    from repro.serve.engine import SparseMatrixEngine
    from repro.serve.router import MicroBatchConfig

    A = make_matrix("cop20k_A", scale=0.005)
    eng = SparseMatrixEngine(
        mesh=_mesh1(),
        micro_batch=MicroBatchConfig(max_batch=4, max_wait_ms=5.0))
    eng.ingest("a", A)
    x = np.random.default_rng(1).standard_normal(A.ncols)
    n_threads, each = 8, 3
    barrier = threading.Barrier(n_threads)

    def hit():
        barrier.wait()
        for _ in range(each):
            eng.spmv("a", x)
    threads = [threading.Thread(target=hit) for _ in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert not any(t.is_alive() for t in threads)
    s = eng.stats()["a"]
    mb = s["micro_batch"]
    assert mb["requests"] == mb["queue_s#"] == n_threads * each
    assert 0 < mb["queue_max_s"] <= mb["queue_s"]
    # one served block per wave, each with its four stages
    assert s["stages_s"]["spmv.wait#"] == mb["batches"]


def test_the_device_step_names_its_slices_and_families():
    """The lowered step's op metadata carries the exchange, the two
    slices, the combine and the kernel family of each switch branch."""
    from repro.core.program import build_program_step, lower
    from repro.core.spmv import SpmvPlan

    A = make_matrix("cop20k_A", scale=0.002)
    prog = lower(A, SpmvPlan(kernel="hyb", distribution="row",
                             num_shards=1))
    step, ops = build_program_step(prog, _mesh1())
    xs = np.zeros((1, prog.x_layout.padded_length()), np.float32)
    text = step.lower(*ops, xs).as_text(debug_info=True)
    for scope in ("spmv.exchange", "spmv.local", "spmv.remote",
                  "spmv.combine", "spmv.remote/cond/branch_2_fun/hyb",
                  "spmv.local/cond/branch_1_fun/seg"):
        assert scope in text, scope
