"""The reduction from a profiler trace to device busy time, idle gaps,
collective time and the per-layer metrics that read them."""
import types

import pytest

from chip_bench import cells, profile_trace
from chip_bench.profile_trace import Event

HOST, TPU0, TPU1 = "/host:CPU", "/device:TPU:0", "/device:TPU:1"
OPS = profile_trace.OPS_LINE


def _ev(plane, line, name, start, end):
    return Event(plane, line, name, start, end - start)


def _hlo(inst, opcode):
    """A device event's name as the TPU profiler writes it: HLO text."""
    return f"%{inst} = f32[8]{{0:T(1024)S(1)}} {opcode}(f32[8]{{0}} %p.1)"


EVENTS = [
    _ev(HOST, "python", "bench.window", 1000, 11000),
    _ev(HOST, "python", "bench.spmv", 1000, 5000),
    _ev(HOST, "python", "bench.wait", 6000, 8000),
    _ev(HOST, "python", "bench.spmv", 8000, 10500),
    _ev(TPU0, OPS, _hlo("copy.0", "copy"), 500, 900),   # before the window
    _ev(TPU0, OPS, _hlo("while.5", "while"), 1500, 4500),
    _ev(TPU0, OPS, _hlo("fusion.1", "fusion"), 1500, 3000),  # in while.5
    _ev(TPU0, OPS, _hlo("gather.2", "gather"), 3000, 4500),  # in while.5
    _ev(TPU0, OPS, _hlo("all-to-all.3", "all-to-all"), 8500, 9000),
    _ev(TPU0, "Async XLA Ops", _hlo("all-gather-start.6", "all-gather-start"),
        8800, 9400),
    _ev(TPU0, "Async XLA Ops", _hlo("copy-start.7", "copy-start"), 1000,
        9000),
    _ev(TPU0, OPS, _hlo("fusion.1", "fusion"), 9000, 10000),
    _ev(TPU0, OPS, _hlo("copy.4", "copy"), 10800, 11500),   # cut at the close
    _ev(TPU0, "XLA Modules", "jit_step", 1500, 10000),
    _ev(TPU1, OPS, _hlo("fusion.1", "fusion"), 2000, 3000),
]


def test_busy_time_is_the_union_of_ops_inside_the_window():
    s = profile_trace.summarize(EVENTS)
    assert s.window == (1000, 11000) and s.window_ns == 10000
    dev = s.busiest()
    assert dev.plane == TPU0
    assert dev.busy == [(1500, 4500), (8500, 10000), (10800, 11000)]
    assert dev.busy_ns == 4700
    assert dev.op_ns == {"while.5 (while)": 0, "fusion.1 (fusion)": 2500,
                         "gather.2 (gather)": 1500,
                         "all-to-all.3 (all-to-all)": 500,
                         "copy.4 (copy)": 200}
    assert dev.collective == [(8500, 9400)] and dev.collective_ns == 900
    assert [d.busy_ns for d in s.devices] == [4700, 1000]


def test_op_names_are_taken_from_the_hlo_text():
    assert profile_trace.op_name(
        "%cond.5.clone.1 = (f32[1,120000,1]{1,2,0:T(1,128)}) conditional("
        "s32[]{:T(128)} %bitcast.55, (f32[1,120000,128]{2,1,0:T(8,128)})") \
        == ("cond.5.clone.1", "conditional")
    assert profile_trace.op_name(
        "%fusion.17 = f32[2641920]{0:T(1024)S(1)} fusion(f32[120000]{0} "
        "%get-tuple-element.28)") == ("fusion.17", "fusion")
    assert profile_trace.op_name("jit_step") == ("jit_step", "")


def test_idle_gaps_are_named_by_the_innermost_host_span():
    s = profile_trace.summarize(EVENTS)
    assert s.idle_gaps(s.busiest()) == [("bench.wait", 4000),
                                        ("bench.spmv", 800),
                                        ("bench.spmv", 500)]
    b = s.breakdown(top=2)
    assert b["device_ops"] == [["fusion.1 (fusion)", 2.5e-6],
                               ["gather.2 (gather)", 1.5e-6]]
    assert b["idle_gaps"] == [["bench.wait", 4e-6], ["bench.spmv", 8e-7]]


def test_a_trace_without_its_window_or_device_ops_is_refused():
    with pytest.raises(ValueError, match="bench.window"):
        profile_trace.summarize(EVENTS[1:])
    with pytest.raises(ValueError, match="no device operation"):
        profile_trace.summarize([e for e in EVENTS if e.plane == HOST])


def _run(trace, done=2, batch=1, chips=1):
    reqs = [types.SimpleNamespace(ok=True, tenant=0)] * done
    return types.SimpleNamespace(
        trace=trace, window=types.SimpleNamespace(requests=reqs,
                                                  batch=batch),
        shapes=[(100, 100, 1000)], chips=chips,
        peak={"hbm_bytes_per_s": 1e9})


def test_trace_metrics_read_the_busiest_device():
    s = profile_trace.summarize(EVENTS)
    assert cells.reader("device_idle_share")(_run(s)) == pytest.approx(53.0)
    assert cells.reader("exchange.collective_ms")(_run(s)) == \
        pytest.approx(900 / 1e6 / 2)
    # floor: (4*1000 + 4*(100+100)) B at 1e9 B/s = 4.8 us a request;
    # busy: 4.7 us over 2 requests.
    assert cells.reader("spmv_roofline")(_run(s)) == \
        pytest.approx(100 * 4.8e-6 / 2.35e-6)
    no_collective = [e for e in EVENTS if "all-" not in e.name]
    assert cells.reader("exchange.collective_ms")(
        _run(profile_trace.summarize(no_collective))) is None
    assert cells.reader("device_idle_share")(_run(None)) is None


def test_an_xplane_file_loads_with_its_host_spans(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: jnp.sin(x) @ x)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        f(x).block_until_ready()
    jax.profiler.stop_trace()
    events = profile_trace.load_xplane(
        sorted(tmp_path.rglob("*.xplane.pb"))[-1])
    windows = [e for e in events if e.name == "bench.window"]
    assert len(windows) == 1 and windows[0].plane == HOST
    assert windows[0].dur > 0


def test_a_gap_is_named_by_the_thread_serving_a_request():
    events = EVENTS + [
        _ev(HOST, "worker", "bench.spmv", 6000, 7000),
        _ev(HOST, "worker", "$program.py:1004 gather_b", 6400, 6600),
    ]
    s = profile_trace.summarize(events)
    assert s.idle_gaps(s.busiest())[0] == ("$program.py:1004 gather_b", 4000)


def test_a_recorded_tpu_trace_reduces_as_on_the_chip():
    """audikw_1_synth.closed1 on one v5e (20 s window, 4 requests), the device
    lines and the Python thread kept: the busy time is what the chip run
    reported, and the remote slice's gather leads the operations."""
    import gzip
    import json
    from pathlib import Path

    path = Path(__file__).with_name("audikw_1_synth_closed1_trace.json.gz")
    with gzip.open(path, "rt") as f:
        events = [Event(*row) for row in json.load(f)]
    s = profile_trace.summarize(events)
    dev = s.busiest()
    assert dev.plane == TPU0 and len(s.devices) == 1
    assert dev.busy_ns == 22_100_571_305 and s.window_ns == 22_145_355_433
    assert dev.collective_ns == 0
    ops = s.breakdown()["device_ops"]
    assert ops[0][0] == "fusion.21 (fusion)" and ops[0][1] > 14.7
    assert sum(ns for ns in dev.op_ns.values()) == dev.busy_ns


def _long_trace(requests, host_per_request):
    """A window of ``requests`` device ops on four devices, each followed
    by an idle gap, under ``host_per_request`` host events apiece."""
    events = [_ev(HOST, "python", "bench.window", 0, 1000 * requests)]
    for i in range(requests):
        t = 1000 * i
        for d in range(4):
            events.append(_ev(f"/device:TPU:{d}", OPS,
                              _hlo(f"fusion.{i % 7}", "fusion"), t + 100,
                              t + 600 + (i % 5)))
        events += [_ev(HOST, f"t{k % 3}", f"$f{k}", t + k, t + k + 50)
                   for k in range(host_per_request)]
    return events


def test_a_long_window_reduces_in_seconds():
    """Thousands of requests, each leaving a gap: the breakdown names only
    its longest gaps, so the reduction grows with the events and not with
    gaps times events (a four-chip closed-loop window answers about 2,000
    requests)."""
    import time
    events = _long_trace(2000, 200)
    t = time.perf_counter()
    s = profile_trace.summarize(events)
    b = s.breakdown()
    assert time.perf_counter() - t < 20
    assert len(b["idle_gaps"]) == 10 and len(b["device_ops"]) == 7
    assert b["idle_gaps"][0][1] == pytest.approx(500e-9)
    assert s.busiest().busy_ns == sum(500 + i % 5 for i in range(2000))
