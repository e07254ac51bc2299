"""Share of the rows the remote kernel pass computes that read a remote x
entry, in %: 100 * sum of ``remote_rows`` over sum of
``remote_pass_rows`` (``stats()[tenant]["exchange"]``), over the cell's
tenants.  The rest of the remote pass's rows wait on the exchange for
nothing.  Nothing is read where no tenant runs a remote pass."""


def read(run):
    ex = [s.get("exchange") or {} for s in run.stats_after.values()]
    rows = sum(sum(e.get("remote_pass_rows", ())) for e in ex)
    if not rows:
        return None
    return 100.0 * sum(sum(e.get("remote_rows", ())) for e in ex) / rows
