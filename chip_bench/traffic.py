"""The one traffic generator: it reads a mix's parameters and drives the
served entry from the client's side.

A mix (``traffic/<name>.json``) gives:

* ``loop``: ``"open"`` (requests sent on a schedule, whatever the server
  does) or ``"closed"`` (each client sends its next request when the last
  one returns);
* ``batch``: vectors per request, B (1 sends (n,) vectors, more sends
  (n, B) blocks);
* open loop: ``schedule``, the module of ``schedules/`` that makes the
  send times from the mix's own parameters (such as ``rate_per_s`` and
  ``arrival_seed``), and ``threads``, the dispatch threads, enough that no
  request waits for one;
* closed loop: ``clients``;
* ``tenant_zipf`` (optional, default 0): where the configuration has
  several tenants, tenant t takes a share of the requests proportional to
  1 / (t + 1) ** tenant_zipf, in an order fixed by the mix;
* ``micro_batch`` (optional): the router's cross-request batching, as
  keyword arguments of ``MicroBatchConfig``;
* ``pool``: how many distinct request vectors (or blocks) are drawn for
  each tenant.

The run's seed draws the request vectors and which one each request sends,
never the arrival times or the tenants, so every seed offers the same work.

Every request is timed by the host clock.  An open-loop request is timed
from the moment it was due, so a stall also delays the requests queued
behind it; a closed-loop one from when it was sent.  The window holds
every request due (open) or sent (closed) before ``seconds`` and closes
when the last of them returns, so no request is cut short and none is
counted in part.
"""
from __future__ import annotations

import concurrent.futures as cf
import contextlib
import dataclasses
import importlib
import threading
import time

import numpy as np

__all__ = ["Request", "Window", "due_times", "tenant_sequence",
           "pool_order", "request_pool", "drive", "percentile"]


@dataclasses.dataclass
class Request:
    """One request of the window; times in seconds from the window start."""

    tenant: int
    pool: int
    due: float
    sent: float = float("nan")
    done: float = float("nan")
    ok: bool = False
    answer: object = None
    error: str | None = None


@dataclasses.dataclass
class Window:
    requests: list
    close: float            # when the last request returned (from start)
    batch: int

    @property
    def attempted(self) -> int:
        return len(self.requests)

    @property
    def failed(self) -> int:
        return sum(not r.ok for r in self.requests)


def percentile(values, q: float) -> float:
    """Nearest-rank percentile: the smallest value with at least ``q``% of
    the sample at or below it."""
    v = np.sort(np.asarray(values, dtype=np.float64))
    if v.size == 0:
        return float("nan")
    k = max(int(np.ceil(q / 100.0 * v.size)) - 1, 0)
    return float(v[k])


def due_times(mix: dict, seconds: float) -> np.ndarray:
    """The open-loop send times of ``mix``, from its ``schedule``."""
    module = importlib.import_module(
        f"chip_bench.schedules.{mix['schedule']}")
    return module.due_times(mix, seconds)


def tenant_sequence(mix: dict, n_tenants: int, n: int) -> np.ndarray:
    """The tenant index of each of ``n`` requests (see ``tenant_zipf``)."""
    if n_tenants == 1:
        return np.zeros(n, dtype=np.int64)
    w = 1.0 / np.arange(1, n_tenants + 1) ** float(mix.get("tenant_zipf", 0))
    rng = np.random.default_rng(np.random.SeedSequence(
        [int(mix.get("arrival_seed", 0)), 5]))
    return rng.choice(n_tenants, size=n, p=w / w.sum())


def pool_order(n_pool: int, seed: int) -> np.ndarray:
    """The order in which requests take the pool's vectors (cycled)."""
    rng = np.random.default_rng(np.random.SeedSequence([seed, 3]))
    return rng.permutation(n_pool)


def request_pool(n: int, batch: int, size: int, seed: int,
                 tenant: int = 0) -> np.ndarray:
    """``size`` float32 request vectors (size, n) or blocks (size, n, B)
    for tenant index ``tenant``."""
    rng = np.random.default_rng(np.random.SeedSequence(
        [seed, 4] + ([tenant] if tenant else [])))
    shape = (size, n) if batch == 1 else (size, n, batch)
    return rng.standard_normal(shape, dtype=np.float32)


def _serve(call, req: Request, t0: float, span) -> Request:
    with span("bench.spmv"):
        req.sent = time.perf_counter() - t0
        try:
            req.answer = call(req)
            req.ok = True
        except Exception as err:        # a failed request is recorded
            req.error = f"{type(err).__name__}: {err}"
        req.done = time.perf_counter() - t0
    return req


def drive(mix: dict, call, seconds: float, seed: int, n_pool: int,
          n_tenants: int = 1, span=None) -> Window:
    """Run one window of ``mix``; ``call(request)`` serves one
    :class:`Request` and returns its answer.  ``span(name)`` makes a host
    trace span (a context manager) around each wait and each request."""
    span = span or (lambda name: contextlib.nullcontext())
    order = pool_order(n_pool, seed)
    if mix["loop"] == "open":
        due = due_times(mix, seconds)
        tenants = tenant_sequence(mix, n_tenants, len(due))
        reqs = [Request(int(tenants[i]), int(order[i % n_pool]), float(d))
                for i, d in enumerate(due)]
        with cf.ThreadPoolExecutor(int(mix["threads"])) as pool:
            t0 = time.perf_counter()
            futures = []
            for req in reqs:
                wait = req.due - (time.perf_counter() - t0)
                if wait > 0:
                    with span("bench.wait"):
                        time.sleep(wait)
                futures.append(pool.submit(_serve, call, req, t0, span))
            for f in futures:
                f.result()
    elif mix["loop"] == "closed":
        reqs, lock = [], threading.Lock()
        tenants = tenant_sequence(mix, n_tenants, 1 << 16)

        def client():
            while True:
                with lock:
                    now = time.perf_counter() - t0
                    if now >= seconds:
                        return
                    k = len(reqs)
                    req = Request(int(tenants[k % tenants.size]),
                                  int(order[k % n_pool]), now)
                    reqs.append(req)
                _serve(call, req, t0, span)

        clients = [threading.Thread(target=client)
                   for _ in range(int(mix["clients"]))]
        t0 = time.perf_counter()
        for c in clients:
            c.start()
        for c in clients:
            c.join()
    else:
        raise ValueError(f"unknown loop {mix['loop']!r}")
    close = max((r.done for r in reqs), default=0.0)
    return Window(requests=reqs, close=close, batch=int(mix["batch"]))

