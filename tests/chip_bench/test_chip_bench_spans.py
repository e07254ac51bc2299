"""The per-layer metrics that read the program's own spans and counters:
the ingest phases, the host's transfers around the device's idle time, and
the router's queue wait.  Each reads nothing, and raises nothing, from a
run of a program that has no such span or counter."""
import gzip
import json
import types
from pathlib import Path

import pytest

from chip_bench import cells, profile_trace
from chip_bench.profile_trace import Event

HOST, TPU0 = "/host:CPU", "/device:TPU:0"
OPS = profile_trace.OPS_LINE


def _ev(plane, line, name, start, end):
    return Event(plane, line, name, start, end - start)


def _hlo(inst):
    return f"%{inst} = f32[8]{{0}} fusion(f32[8]{{0}} %p.1)"


#: A window of 10 us; the device busy in [1, 3) and [6, 8) us.  Transfer
#: spans on two serving threads: partly over busy time, wholly in idle
#: time, two that overlap each other, and one that starts before the
#: window.  ``spmv.wait`` is idle time that no transfer accounts for.
EVENTS = [
    _ev(HOST, "main", "bench.window", 0, 10000),
    _ev(TPU0, OPS, _hlo("fusion.1"), 1000, 3000),
    _ev(TPU0, OPS, _hlo("fusion.2"), 6000, 8000),
    _ev(HOST, "t0", "spmv.scatter_x", -500, 200),
    _ev(HOST, "t0", "spmv.scatter_x", 500, 1500),
    _ev(HOST, "t0", "spmv.gather_b", 3000, 4000),
    _ev(HOST, "t1", "spmv.gather_b", 3500, 4500),
    _ev(HOST, "t0", "spmv.wait", 4500, 6000),
    _ev(HOST, "t1", "spmv.put", 8500, 9000),
    _ev(HOST, "t1", "$program.py:1004 gather_b", 3600, 3700),
]


def _run(trace, done=2, before=None, after=None):
    reqs = [types.SimpleNamespace(ok=True, tenant=0)] * done
    return types.SimpleNamespace(
        trace=trace, window=types.SimpleNamespace(requests=reqs, batch=1),
        stats_before=before or {}, stats_after=after or {})


def test_host_transfer_ms_sums_the_transfer_spans_per_request():
    s = profile_trace.summarize(EVENTS)
    # 200 + 1000 + 1000 + 1000 + 500 ns over two requests
    assert cells.reader("host.transfer_ms")(_run(s)) == \
        pytest.approx(3700 / 1e6 / 2)


def test_transfer_idle_share_counts_idle_time_inside_a_transfer_only():
    s = profile_trace.summarize(EVENTS)
    # idle inside transfers: [0, 200) + [500, 1000) + [3000, 4500)
    # + [8500, 9000) = 2700 ns; the idle [4500, 6000) under spmv.wait and
    # the rest of the idle time count for device_idle_share alone.
    share = cells.reader("host.transfer_idle_share")(_run(s))
    assert share == pytest.approx(27.0)
    assert share <= cells.reader("device_idle_share")(_run(s)) == \
        pytest.approx(60.0)


def test_a_transfer_over_busy_time_is_no_idle_share():
    events = [e for e in EVENTS if not e.name.startswith("spmv.")] + [
        _ev(HOST, "t0", "spmv.put", 1200, 2800)]
    s = profile_trace.summarize(events)
    assert cells.reader("host.transfer_idle_share")(_run(s)) == 0.0
    assert cells.reader("host.transfer_ms")(_run(s)) == \
        pytest.approx(1600 / 1e6 / 2)


STATS = {"a": {"ingest_phases_s": {
            "ingest.plan": 1.0, "ingest.plan#": 1, "ingest.lower": 2.0,
            "ingest.lower#": 1, "ingest.stack": 3.0, "ingest.stack#": 1,
            "ingest.place": 0.5, "ingest.place#": 1}},
         "b": {"ingest_phases_s": {
            "ingest.plan": 0.25, "ingest.plan#": 1, "ingest.stack": 1.0,
            "ingest.stack#": 1, "ingest.place": 0.5, "ingest.place#": 1}}}


@pytest.mark.parametrize("phase, seconds", [
    ("plan", 1.25), ("lower", 2.0), ("stack", 4.0), ("place", 1.0)])
def test_ingest_phases_sum_over_tenants(phase, seconds):
    run = _run(None, after=STATS)
    assert cells.reader(f"ingest.{phase}_s")(run) == seconds


def test_router_queue_ms_is_the_mean_wait_in_the_window():
    def mb(s, n):
        return {"micro_batch": {"requests": n, "batches": 1, "widest": 4,
                                "queue_s": s, "queue_s#": n,
                                "queue_max_s": 0.1}}
    run = _run(None, before={"a": mb(1.0, 10), "b": mb(0.0, 0)},
               after={"a": mb(1.5, 20), "b": mb(0.1, 10)})
    assert cells.reader("router.queue_ms")(run) == pytest.approx(
        1e3 * 0.6 / 20)
    idle = _run(None, before={"a": mb(1.0, 10)}, after={"a": mb(1.0, 10)})
    assert cells.reader("router.queue_ms")(idle) is None


NEW = ("ingest.plan_s", "ingest.lower_s", "ingest.stack_s",
       "ingest.place_s", "host.transfer_ms", "host.transfer_idle_share",
       "router.queue_ms")


def _fixture_trace():
    path = Path(__file__).with_name("audikw_1_synth_closed1_trace.json.gz")
    with gzip.open(path, "rt") as f:
        return profile_trace.summarize([Event(*row) for row in json.load(f)])


@pytest.mark.parametrize("metric", NEW)
def test_a_program_without_spans_gives_nothing_to_read(metric):
    """The recorded trace and the counters of a program that has neither
    spans nor span counters, as the benchmark's traced run sees them."""
    bare = {"a": {"spmv_count": 4, "micro_batch": {
        "requests": 4, "batches": 4, "widest": 1}}}
    run = _run(_fixture_trace(), done=4, before=bare, after=bare)
    assert cells.reader(metric)(run) is None


def test_the_existing_readers_read_the_recorded_trace_as_before():
    """audikw_1_synth.closed1 on one v5e, 4 requests: the device's idle
    share and the roofline share read what they read on the chip."""
    run = _run(_fixture_trace(), done=4)
    run.shapes, run.chips = [(943_000, 943_000, 79_939_030)], 1
    run.peak = {"hbm_bytes_per_s": 819e9}
    assert cells.reader("device_idle_share")(run) == pytest.approx(
        100 * (1 - 22_100_571_305 / 22_145_355_433), rel=1e-12)
    floor_s = 4 * (4 * 79_939_030 + 4 * 2 * 943_000) / 819e9
    assert cells.reader("spmv_roofline")(run) == pytest.approx(
        100 * floor_s / 22.100571305, rel=1e-12)
