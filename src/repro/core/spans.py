"""Named spans and running counters on the profiler's clock.

``span(name, into, **ids)`` marks a stretch of host code in two ways at
once.  It is a :class:`jax.profiler.TraceAnnotation`, so a profiler trace
of the process holds it as a host event, on the same clock as the
device's operations, with ``ids`` as its stats.  And where ``into`` is a
dict, it adds the stretch's ``perf_counter`` seconds to ``into[name]``
and one call to ``into[name + "#"]``.  Nothing is kept per event: the
counters are running sums, and an annotation costs next to nothing
while no profiler runs, so spans stay on.
"""
from __future__ import annotations

import contextlib
import time

import jax

__all__ = ["span", "add_to"]


def add_to(into: dict, name: str, seconds: float, calls: int = 1) -> None:
    """Add ``seconds`` and ``calls`` to ``into``'s counters of ``name``."""
    into[name] = into.get(name, 0.0) + seconds
    into[name + "#"] = into.get(name + "#", 0) + calls


class span:
    """A host span ``name`` in the profiler trace, counted into ``into``.

    ``lock`` guards ``into`` where several threads count into it."""

    __slots__ = ("_name", "_into", "_lock", "_ann", "_t")

    def __init__(self, name: str, into: dict | None = None, lock=None,
                 **ids):
        self._name, self._into, self._lock = name, into, lock
        self._ann = jax.profiler.TraceAnnotation(name, **ids)

    def __enter__(self):
        self._ann.__enter__()
        self._t = time.perf_counter()
        return self

    def __exit__(self, *exc):
        dt = time.perf_counter() - self._t
        self._ann.__exit__(*exc)
        if self._into is not None:
            with self._lock or contextlib.nullcontext():
                add_to(self._into, self._name, dt)
        return False
