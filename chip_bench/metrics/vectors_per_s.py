"""Answered vectors over the window: requests times B, over the seconds
from the window's start to the return of the last request sent in it."""


def read(run):
    w = run.window
    if w.close <= 0:
        return None
    return sum(r.ok for r in w.requests) * w.batch / w.close
