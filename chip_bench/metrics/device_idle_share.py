"""Share of the traced window in which no operation ran on the busiest
device, in %."""


def read(run):
    t = run.trace
    if t is None:
        return None
    return 100.0 * (1.0 - t.busiest().busy_ns / t.window_ns)
