"""KiB of x one answered vector's collective moves between chips, padded
as sent (``stats()[tenant]["exchange"]["sent_entries"]`` float32
entries), averaged over the window's answered requests.  Nothing is read
where no tenant exchanges, as in a program with one kernel pass."""


def read(run):
    sent = [(s.get("exchange") or {}).get("sent_entries", 0)
            for s in run.stats_after.values()]
    done = [r for r in run.window.requests if r.ok]
    if not any(sent) or not done:
        return None
    return 4 / 1024 * sum(sent[r.tenant] for r in done) / len(done)
