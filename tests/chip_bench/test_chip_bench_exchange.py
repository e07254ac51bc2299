"""The per-layer metrics of the sharded deployment: the chips' busy
imbalance from a trace of four device planes, and the exchange's counters
(``stats()[tenant]["exchange"]``).  Each reads nothing, and raises
nothing, on one chip or from a program with one kernel pass."""
import gzip
import json
import types
from pathlib import Path

import pytest

from chip_bench import cells, profile_trace
from chip_bench.profile_trace import Event

HOST = "/host:CPU"
OPS = profile_trace.OPS_LINE


def _hlo(inst, opcode="fusion"):
    return f"%{inst} = f32[8]{{0}} {opcode}(f32[8]{{0}} %p.1)"


def _planes(busy_us):
    """A 10 us window and one device plane per entry of ``busy_us``, each
    busy for its list of [start, end) us intervals."""
    events = [Event(HOST, "main", "bench.window", 0, 10000)]
    for i, intervals in enumerate(busy_us):
        for k, (s, e) in enumerate(intervals):
            events.append(Event(f"/device:TPU:{i}", OPS, _hlo(f"fusion.{k}"),
                                s * 1000, (e - s) * 1000))
    return profile_trace.summarize(events)


def _run(trace=None, chips=4, after=None, done=3, tenants=None):
    reqs = [types.SimpleNamespace(ok=True, tenant=t)
            for t in (tenants or [0] * done)]
    return types.SimpleNamespace(
        trace=trace, chips=chips, stats_after=after or {},
        window=types.SimpleNamespace(requests=reqs, batch=1))


def _exchange(sent=0, needed=0, remote=(0,) * 4, pass_rows=(0,) * 4,
              nnz=(0,) * 4):
    return {"device_passes": 2 if sent else 1, "exchange": {
        "sent_entries": sent, "needed_entries": needed,
        "remote_rows": list(remote), "remote_pass_rows": list(pass_rows),
        "shard_nnz": list(nnz)}}


IMBALANCE = cells.reader("device.busy_imbalance")
SENT_KIB = cells.reader("exchange.sent_kib")
REMOTE_SHARE = cells.reader("kernels.remote_rows_share")


@pytest.mark.parametrize("busy_us, expected", [
    # busy 4, 2, 2, 2 us: mean 2.5, the busiest 1.6 times it
    ([[(0, 4)], [(1, 3)], [(2, 4)], [(5, 7)]], 60.0),
    # overlapping operations count once: 3, 3, 3, 3 us
    ([[(0, 2), (1, 3)], [(0, 3)], [(4, 7)], [(7, 8), (8, 10)]], 0.0),
    # a chip that ran nothing in the window counts as idle: 2, 2, 2, 0
    ([[(0, 2)], [(2, 4)], [(4, 6)]], 100.0 * (2 / 1.5 - 1)),
], ids=["one-busier", "equal", "one-idle"])
def test_busy_imbalance_reads_the_busiest_chip_over_the_mean(busy_us,
                                                             expected):
    assert IMBALANCE(_run(_planes(busy_us))) == pytest.approx(expected)


def test_equal_planes_read_no_imbalance():
    assert IMBALANCE(_run(_planes([[(1, 6)]] * 4))) == 0.0


def test_busy_imbalance_reads_nothing_on_one_chip_or_untraced():
    path = Path(__file__).with_name("audikw_1_synth_closed1_trace.json.gz")
    with gzip.open(path, "rt") as f:
        recorded = profile_trace.summarize(
            [Event(*row) for row in json.load(f)])
    assert len(recorded.devices) == 1
    assert IMBALANCE(_run(recorded, chips=1)) is None
    assert IMBALANCE(_run(_planes([[(0, 4)]]), chips=1)) is None
    assert IMBALANCE(_run(None)) is None


def test_sent_kib_is_the_padded_collective_per_answered_vector():
    # the x4 cell's all-to-all: 4 * 4 shards * H = 2,561 float32
    after = {"t": _exchange(sent=16 * 2561, needed=12256)}
    assert SENT_KIB(_run(after=after)) == pytest.approx(16 * 2561 * 4 / 1024)


def test_sent_kib_weighs_each_tenant_by_its_answered_requests():
    after = {"a": _exchange(sent=1024), "b": _exchange()}
    # three requests to a (4 KiB each), one to b (nothing sent)
    assert SENT_KIB(_run(after=after, tenants=[0, 0, 1, 0])) == \
        pytest.approx(3.0)


def test_remote_rows_share_is_the_remote_pass_rows_that_read_remotely():
    after = {"t": _exchange(sent=512, remote=(30, 50, 42, 30),
                            pass_rows=(512,) * 4)}
    assert REMOTE_SHARE(_run(after=after)) == \
        pytest.approx(100 * 152 / 2048)
    two = {"a": after["t"], "b": _exchange(sent=64, remote=(8, 0, 0, 0),
                                           pass_rows=(16,) * 4)}
    assert REMOTE_SHARE(_run(after=two)) == \
        pytest.approx(100 * 160 / 2112)


@pytest.mark.parametrize("after", [
    {"t": _exchange()},                        # one kernel pass: all zeros
    {"t": {"device_passes": 1}},               # a program with no counters
    {},                                        # no tenant stats at all
], ids=["one-pass", "no-counters", "empty"])
def test_counters_read_nothing_without_an_exchange(after):
    assert SENT_KIB(_run(after=after)) is None
    assert REMOTE_SHARE(_run(after=after)) is None


def test_sent_kib_reads_nothing_without_an_answered_request():
    after = {"t": _exchange(sent=512)}
    assert SENT_KIB(_run(after=after, done=0, tenants=[])) is None
