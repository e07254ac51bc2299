"""One run of one cell: set-up, the measured window, the check, the metrics.

Set-up builds each of the configuration's tenants' matrices from the
seed, ingests them cold into one ``SparseMatrixEngine`` on a 1-D mesh of
the cell's chips (axis ``"model"``), and calls every program shape the
mix will use.  The window then drives ``engine.spmv`` from the client's
side.  After it, the
device's peak memory is read, the engine is dropped, and every answer of
the window is compared with the float64 reference.
"""
from __future__ import annotations

import dataclasses
import gc
import json
import shutil
import sys
import tempfile
import threading
import time
from pathlib import Path

import numpy as np

from chip_bench import cells, profile_trace, reference, roofline, traffic
from chip_bench.matrix import build_matrix

__all__ = ["Run", "Cell", "run_cell"]

#: Seconds of back-to-back requests at the end of set-up, after every
#: program shape has run once: the first calls of a fresh process ran
#: slower than later ones with nothing left to compile.
WARM_S = 3.0


@dataclasses.dataclass
class Run:
    """What a metric reader sees of one run."""

    window: traffic.Window
    setup_s: float
    ingest_s: float
    stats_before: dict
    stats_after: dict
    trace: profile_trace.TraceSummary | None
    shapes: list        # (nrows, ncols, nnz) of each tenant, by index
    chips: int
    peak: dict


def info(**kw) -> None:
    """An information line on standard output (never the last one)."""
    print(json.dumps(kw, default=str), flush=True)


class _CompileCounter:
    """Counts JAX traces and compilations inside a ``with`` block."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        self.count = 0

    def _event(self, event, duration, **kw):
        if event in self.EVENTS:
            self.count += 1

    def __enter__(self):
        import jax
        jax.monitoring.register_event_duration_secs_listener(self._event)
        return self

    def __exit__(self, *exc):
        import jax
        jax.monitoring.unregister_event_duration_listener(self._event)


class _GcPauses:
    """Python's garbage collections inside a ``with`` block: the
    generation and seconds of each, and a host trace span
    ``python.gc<generation>`` around it, so that a device idle gap that a
    collection causes is named by it in the breakdown."""

    def __init__(self):
        self.pauses = []
        self._open = {}

    def _callback(self, phase, info):
        import jax
        tid = threading.get_ident()
        if phase == "start":
            span = jax.profiler.TraceAnnotation(
                f"python.gc{info['generation']}")
            span.__enter__()
            self._open[tid] = (time.perf_counter(), span)
        elif tid in self._open:
            t, span = self._open.pop(tid)
            span.__exit__(None, None, None)
            self.pauses.append((info["generation"], time.perf_counter() - t))

    def __enter__(self):
        gc.callbacks.append(self._callback)
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self._callback)

    def summary(self) -> dict:
        return {"gc_collections": [sum(g == n for g, _ in self.pauses)
                                   for n in range(3)],
                "gc_pause_ms_max": 1e3 * max((d for _, d in self.pauses),
                                             default=0.0),
                "gc_pause_ms_total": 1e3 * sum(d for _, d in self.pauses)}


def _hbm_peak(devices) -> int | None:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


@dataclasses.dataclass
class Tenant:
    """One tenant of a cell: its configuration entry, its matrix and its
    request vectors."""

    name: str
    spec: dict
    A: object
    pool: np.ndarray


class Cell:
    """A cell set up for measuring: its tenants' matrices, request pools
    and the engine that serves them."""

    def __init__(self, bench: cells.Benchmark, name: str, devices,
                 seed: int):
        import jax
        from jax.sharding import AxisType
        from repro.core.sparse_matrix import CSRMatrix
        from repro.core.spmv import SpmvPlan
        from repro.serve.router import MESH_AXIS, MicroBatchConfig, \
            SparseMatrixEngine

        self.bench, self.name, self.seed = bench, name, seed
        self.cell = bench.cell(name)
        self.config = bench.config(self.cell["config"])
        self.mix = bench.traffic(self.cell["traffic"])
        self.chips = int(self.cell["chips"])
        if int(self.config["chips"]) != self.chips:
            raise ValueError(f"{name}: the cell asks for {self.chips} chips,"
                             f" its configuration for {self.config['chips']}")
        self.devices = list(devices)[:self.chips]
        on_chip = self.devices[0].platform != "cpu"
        self.peak = roofline.peak(self.devices[0].device_kind) \
            if on_chip else {}
        mesh = jax.make_mesh((self.chips,), (MESH_AXIS,),
                             axis_types=(AxisType.Auto,),
                             devices=self.devices)

        t = time.perf_counter()
        self.tenants = []
        for i, spec in enumerate(self.config["tenants"]):
            A = build_matrix(spec, seed, i)
            pool = traffic.request_pool(A.ncols, int(self.mix["batch"]),
                                        int(self.mix["pool"]), seed, i)
            self.tenants.append(Tenant(spec["name"], spec, A, pool))
        info(phase="generate", rows=[x.A.nrows for x in self.tenants],
             nnz=[x.A.nnz for x in self.tenants], s=time.perf_counter() - t)

        micro = self.mix.get("micro_batch")
        self.engine = SparseMatrixEngine(
            mesh=mesh, micro_batch=MicroBatchConfig(**micro) if micro
            else None)
        self.ingest_s = 0.0
        for tenant in self.tenants:
            A, plan = tenant.A, tenant.spec.get("plan")
            served = CSRMatrix(shape=A.shape, values=A.values.copy(),
                               col_index=A.col_index.copy(),
                               row_ptr=A.row_ptr.copy())
            t = time.perf_counter()
            self.engine.ingest(tenant.name, served,
                               SpmvPlan(**plan) if plan else None)
            self.ingest_s += time.perf_counter() - t
            stats = self.engine.stats()[tenant.name]
            info(phase="ingest", tenant=tenant.name,
                 s=time.perf_counter() - t, plan=stats["plan"],
                 shard_kernels=stats["shard_kernels"],
                 shard_exchanges=stats["shard_exchanges"],
                 device_operand_bytes=stats.get("device_operand_bytes"))
        t = time.perf_counter()
        self._warm()
        info(phase="warm", s=time.perf_counter() - t)

    def stats(self) -> dict:
        """The engine's counters of each tenant, by name."""
        every = self.engine.stats()
        return {t.name: every[t.name] for t in self.tenants}

    def _warm(self) -> None:
        """Call every program shape the mix sends once, for each tenant:
        the widths of the micro-batched waves, else the request's own
        shape; then requests through the router, back to back, for
        ``WARM_S`` seconds (one to each tenant at least), so that the
        host's path is warm too."""
        import jax
        from repro.core.program import scatter_x

        micro = self.mix.get("micro_batch")
        for tenant in self.tenants if micro else ():
            fn = self.engine.device_fn(tenant.name)
            for k in range(1, int(micro.get("max_batch", 8)) + 1):
                X = np.repeat(tenant.pool[0][:, None], k, axis=1)
                jax.block_until_ready(fn(scatter_x(fn.program, X)))
        t, i = time.perf_counter(), 0
        while True:
            for tenant in self.tenants:
                self.engine.spmv(tenant.name,
                                 tenant.pool[i % len(tenant.pool)])
            i += 1
            if time.perf_counter() - t >= WARM_S:
                break

    def measure(self, mix: dict, seconds: float, trace_dir: Path | None
                ) -> tuple:
        """One window of ``mix``: (window, stats before, stats after,
        compilations inside it).  With ``trace_dir`` the profiler traces
        it into that directory."""
        import jax

        def call(req):
            tenant = self.tenants[req.tenant]
            return self.engine.spmv(tenant.name, tenant.pool[req.pool])

        before = self.stats()
        with _CompileCounter() as compiles, _GcPauses() as pauses:
            if trace_dir is not None:
                jax.profiler.start_trace(str(trace_dir))
            with jax.profiler.TraceAnnotation(profile_trace.WINDOW_SPAN):
                window = traffic.drive(mix, call, seconds, self.seed,
                                       int(mix["pool"]), len(self.tenants),
                                       span=jax.profiler.TraceAnnotation)
            if trace_dir is not None:
                jax.profiler.stop_trace()
        late = [r.sent - r.due for r in window.requests]
        info(phase="window", rate_per_s=mix.get("rate_per_s"),
             attempted=window.attempted, failed=window.failed,
             close_s=window.close, compiles_in_window=compiles.count,
             generator_late_ms_p50=1e3 * traffic.percentile(late, 50),
             generator_late_ms_max=1e3 * max(late, default=0.0),
             **pauses.summary(),
             first_error=next((r.error for r in window.requests
                               if r.error), None))
        return window, before, self.stats(), compiles.count

    def release(self) -> int | None:
        """Drop the engine and its device state; returns the peak device
        memory of the fullest chip, read before."""
        hbm = _hbm_peak(self.devices)
        self.engine = None
        gc.collect()
        return hbm

    def check(self, windows) -> dict:
        """Every answer of ``windows`` against the float64 reference: the
        numbers compared, each with its limit."""
        refs = [reference.Reference(t.A) for t in self.tenants]
        answers: dict = {}
        worst, failed = 0.0, 0
        for w in windows:
            failed += w.failed
            for r in w.requests:
                if not r.ok:
                    continue
                key = (r.tenant, r.pool)
                if key not in answers:
                    answers[key] = refs[r.tenant].answer(
                        self.tenants[r.tenant].pool[r.pool])
                worst = max(worst, refs[r.tenant].norm_err(r.answer,
                                                           *answers[key]))
        limit = float(self.config["limits"]["max_norm_err"])
        return {"max_norm_err": {"value": worst, "limit": limit},
                "failed": {"value": failed, "limit": 0}}


def run_cell(bench: cells.Benchmark, name: str, devices, seed: int,
             seconds: float, traced: bool, t_process: float,
             keep_trace: Path | None = None) -> dict:
    """Run cell ``name`` once on ``devices``; returns the result line."""
    cell = Cell(bench, name, devices, seed)
    setup_s = time.perf_counter() - t_process
    info(phase="setup", setup_s=setup_s)
    trace_dir = Path(tempfile.mkdtemp(prefix="chip_bench_trace_")) \
        if traced else None
    try:
        window, before, after, _ = cell.measure(cell.mix, seconds, trace_dir)
        hbm = cell.release()
        summary = None
        if traced:
            if keep_trace is not None:
                shutil.copytree(trace_dir, keep_trace, dirs_exist_ok=True)
            pb = sorted(trace_dir.rglob("*.xplane.pb"))
            summary = profile_trace.summarize(
                profile_trace.load_xplane(pb[-1]))
    finally:
        if trace_dir is not None:
            shutil.rmtree(trace_dir, ignore_errors=True)
    checks = cell.check([window])

    run = Run(window=window, setup_s=setup_s, ingest_s=cell.ingest_s,
              stats_before=before, stats_after=after,
              trace=summary,
              shapes=[(t.A.nrows, t.A.ncols, t.A.nnz) for t in cell.tenants],
              chips=cell.chips, peak=cell.peak)
    metrics = {}
    for m in bench.metrics(name, traced):
        value = cells.reader(m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    dev = cell.devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": cell.chips, "memory_peak_bytes": hbm or 0}
    result = {"correct": all(c["value"] <= c["limit"]
                             for c in checks.values()),
              "attempted": window.attempted, "failed": window.failed,
              "metrics": metrics, "device": device}
    if summary is not None:
        device["busy_s"] = sum(d.busy_ns for d in summary.devices) / \
            (1e9 * cell.chips)
        device["window_s"] = summary.window_ns / 1e9
        result["breakdown"] = summary.breakdown()
    for k, c in checks.items():
        print(f"check {k} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    result["checks"] = checks
    return result
