"""Device time of the collective operations (all-to-all, all-gather, ...)
per request on the busiest device, in ms: the union of their spans on the
``XLA Ops`` and ``Async XLA Ops`` lines.  Nothing is read where no
collective ran, as on one chip."""


def read(run):
    t = run.trace
    done = sum(r.ok for r in run.window.requests)
    if t is None or not done:
        return None
    ns = t.busiest().collective_ns
    return ns / 1e6 / done if ns else None
