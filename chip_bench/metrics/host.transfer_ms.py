"""Host milliseconds per answered request inside the spans that move x and
y between host and device (``spmv.scatter_x``, ``spmv.put``,
``spmv.gather_b``) in the traced window, summed over every serving
thread."""
from chip_bench.program_spans import TRANSFER_SPANS


def read(run):
    t = run.trace
    done = sum(r.ok for r in run.window.requests)
    if t is None or not done:
        return None
    w0, w1 = t.window
    ns = [min(e.end, w1) - max(e.start, w0) for e in t.host
          if e.name in TRANSFER_SPANS]
    return sum(ns) / 1e6 / done if ns else None
