"""95th percentile (nearest rank) of the latency of every request due in
the window, from its due time to its answer in the caller's order, in ms.
A failed request counts as never answered; where more than 5% failed there
is no finite percentile and nothing is reported."""
import math

from chip_bench.traffic import percentile


def read(run):
    lat = [(r.done - r.due) if r.ok else math.inf
           for r in run.window.requests]
    p95 = percentile(lat, 95)
    return 1e3 * p95 if math.isfinite(p95) else None
