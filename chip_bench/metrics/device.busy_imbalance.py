"""How far the busiest chip's busy time lies above the mean over the
cell's chips, in %: 100 * (busiest / mean - 1), from the traced window's
device planes (a chip with no operation in the window counts as idle).
The static split balances stored entries, so this reads the imbalance the
chips build up while they serve.  Nothing is read on one chip."""


def read(run):
    t = run.trace
    if t is None or run.chips < 2:
        return None
    mean = sum(d.busy_ns for d in t.devices) / run.chips
    return 100.0 * (t.busiest().busy_ns / mean - 1.0) if mean else None
