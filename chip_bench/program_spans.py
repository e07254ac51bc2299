"""Read the program's own spans and counters (``repro.core.spans``) out of
a run: the per-tenant counters that ``SparseMatrixEngine.stats()`` gives,
and the host spans of a traced window.

A program that has none of them gives ``None`` from every function here,
and raises nothing, so a reader of one of them leaves its metric out of
the result line.
"""
from __future__ import annotations

from chip_bench.profile_trace import _merge

__all__ = ["TRANSFER_SPANS", "ingest_phase_s", "counter_delta",
           "host_intervals", "overlap_ns"]

#: The stages of a served block that move x or y between host and device
#: while the device waits: the permute and split of x, its copy to the
#: device with the step's dispatch, and the copy back with the unpermute.
TRANSFER_SPANS = ("spmv.scatter_x", "spmv.put", "spmv.gather_b")


def ingest_phase_s(run, phase: str) -> float | None:
    """Seconds of ingest phase ``phase`` (the span ``ingest.<phase>``),
    summed over the run's tenants."""
    phases = [s["ingest_phases_s"] for s in run.stats_after.values()
              if "ingest_phases_s" in s]
    if not phases:
        return None
    return sum(p.get(f"ingest.{phase}", 0.0) for p in phases)


def counter_delta(run, group: str, key: str) -> float | None:
    """How far the counter ``stats()[tenant][group][key]`` moved over the
    window, summed over the run's tenants."""
    moved = [after[group][key] - run.stats_before[name][group][key]
             for name, after in run.stats_after.items()
             if key in (after.get(group) or {})]
    return sum(moved) if moved else None


def host_intervals(trace, names) -> list:
    """Merged [start, end) ns of the host spans named ``names`` inside the
    window of ``trace``."""
    w0, w1 = trace.window
    return _merge((max(e.start, w0), min(e.end, w1)) for e in trace.host
                  if e.name in names)


def overlap_ns(a, b) -> int:
    """ns covered by both of two lists of merged, sorted intervals."""
    total, i, j = 0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        total += max(0, hi - lo)
        if a[i][1] <= b[j][1]:
            i += 1
        else:
            j += 1
    return total
