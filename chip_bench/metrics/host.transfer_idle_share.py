"""Share of the traced window in which the busiest device is idle while a
serving thread is inside a span that moves x or y between host and device
(``spmv.scatter_x``, ``spmv.put``, ``spmv.gather_b``), in %: the part of
``device_idle_share`` that the host's transfers account for."""
from chip_bench.program_spans import TRANSFER_SPANS, host_intervals, \
    overlap_ns


def read(run):
    t = run.trace
    if t is None:
        return None
    spans = host_intervals(t, TRANSFER_SPANS)
    if not spans:
        return None
    idle = sum(e - s for s, e in spans) - overlap_ns(spans, t.busiest().busy)
    return 100.0 * idle / t.window_ns
