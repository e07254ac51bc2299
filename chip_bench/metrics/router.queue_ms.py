"""Mean wait of a micro-batched request in the router's queue in the
window, in ms: from its submit to the start of its wave, from the engine's
``micro_batch`` counters ``queue_s`` and ``queue_s#`` of every tenant,
read before and after it."""
from chip_bench.program_spans import counter_delta


def read(run):
    s = counter_delta(run, "micro_batch", "queue_s")
    n = counter_delta(run, "micro_batch", "queue_s#")
    return 1e3 * s / n if n else None
