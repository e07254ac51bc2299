"""Operand-set reads a served request costs a shard over the window: the
differences of ``stats()[tenant]["kernels"]["operand_reads"]`` and
``["requests"]`` across the window, summed over the cell's tenants, one
over the other.  A request of one vector reads each pass's operands once;
a block of B reads them once where every family of the program takes the
block whole, B times where the step loops over its vectors.  Nothing is
read where no tenant reports the counter, or no request was served."""


def read(run):
    reads = requests = 0
    for name, after in run.stats_after.items():
        a = after.get("kernels") or {}
        if "operand_reads" not in a:
            continue
        b = run.stats_before.get(name, {}).get("kernels") or {}
        reads += a["operand_reads"] - b.get("operand_reads", 0)
        requests += a["requests"] - b.get("requests", 0)
    return reads / requests if requests else None
