"""The kernel layer's read counter metric, ``kernels.operand_reads``:
operand-set reads per served request over the window, from the window's
differences of ``stats()[tenant]["kernels"]["operand_reads"]`` and
``["requests"]``.  It reads nothing, and raises nothing, where no tenant
reports the counter or no request was served."""
import types

import pytest

from chip_bench import cells

READS = cells.reader("kernels.operand_reads")


def _run(before, after):
    return types.SimpleNamespace(stats_before=before, stats_after=after,
                                 trace=None, chips=1)


def _k(reads, requests):
    return {"device_passes": 1, "kernels": {"scatter_updates": [9],
                                            "operand_reads": reads,
                                            "requests": requests}}


@pytest.mark.parametrize("before, after, expected", [
    # closed8: blocks of 8 taken whole by a seg program, one read each
    ({"t": _k(40, 40)}, {"t": _k(240, 240)}, 1.0),
    # the same blocks through a step that loops over the vectors
    ({"t": _k(320, 40)}, {"t": _k(1920, 240)}, 8.0),
    # a two-pass program serving single vectors
    ({"t": _k(0, 0)}, {"t": _k(600, 300)}, 2.0),
    # two tenants: reads and requests summed, then divided
    ({"a": _k(0, 0), "b": _k(0, 0)}, {"a": _k(10, 10), "b": _k(80, 10)},
     4.5),
    # a tenant without the counter (as before it existed) adds nothing
    ({"a": _k(2, 2), "b": {"device_passes": 1}},
     {"a": _k(12, 7), "b": {"device_passes": 1}}, 2.0),
], ids=["whole-block", "looped-block", "two-pass", "two-tenants", "mixed"])
def test_operand_reads_per_request_in_the_window(before, after, expected):
    assert READS(_run(before, after)) == expected


@pytest.mark.parametrize("before, after", [
    ({"t": {"device_passes": 1, "kernels": {"scatter_updates": [9]}}},
     {"t": {"device_passes": 1, "kernels": {"scatter_updates": [9]}}}),
    ({"t": {"device_passes": 1}}, {"t": {"device_passes": 1}}),
    ({"t": _k(5, 5)}, {"t": _k(5, 5)}),          # nothing served
    ({}, {}),
], ids=["no-read-key", "no-kernels", "no-requests", "empty"])
def test_operand_reads_reads_nothing_without_the_counter(before, after):
    assert READS(_run(before, after)) is None
