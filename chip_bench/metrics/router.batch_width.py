"""Mean width of the router's micro-batched waves in the window: requests
over batches, from the engine's own counters of every tenant, read before
and after it."""


def read(run):
    requests = batches = 0
    for name, after in run.stats_after.items():
        a, b = after.get("micro_batch"), run.stats_before[name].get(
            "micro_batch")
        if a:
            requests += a["requests"] - b["requests"]
            batches += a["batches"] - b["batches"]
    return requests / batches if batches else None
