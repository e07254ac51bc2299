"""Scatter-add updates one vector's device step runs on the busiest shard:
``stats()[tenant]["kernels"]["scatter_updates"]`` (per shard, summed over
the step's kernel passes), summed over the cell's tenants shard by shard,
then the largest.  A seg or split pass scatters one update per (row,
chunk) piece, a hyb pass one per overflow entry.  Nothing is read where
no tenant reports the counter."""


def read(run):
    counts = [(s.get("kernels") or {}).get("scatter_updates")
              for s in run.stats_after.values()]
    counts = [c for c in counts if c is not None]
    if not counts:
        return None
    width = max(len(c) for c in counts)
    return float(max(sum(c[i] for c in counts if i < len(c))
                     for i in range(width)))
