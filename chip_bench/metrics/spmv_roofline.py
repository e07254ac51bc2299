"""The served SpMV's share of the HBM roofline, in %: the floor time of
the window's answered requests over the busiest device's busy time.

A request's floor time is its floor bytes (``roofline.floor_bytes`` of its
tenant's matrix: float32 values once, x and y once, no index bytes) over
the peak HBM bandwidth of all the cell's chips.  The busy time counts
every operation on the device, so this is the whole served step's share,
not one kernel's, and it cannot read over 100% for any layout."""
from chip_bench.roofline import floor_bytes


def read(run):
    t = run.trace
    done = [r for r in run.window.requests if r.ok]
    if t is None or not done or not run.peak:
        return None
    floor = sum(floor_bytes(nnz, nrows, ncols, run.window.batch)
                for nrows, ncols, nnz in (run.shapes[r.tenant] for r in done))
    floor_s = floor / (run.chips * run.peak["hbm_bytes_per_s"])
    return 100.0 * floor_s / (t.busiest().busy_ns / 1e9)
