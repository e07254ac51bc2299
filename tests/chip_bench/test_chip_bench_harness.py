"""The harness on the CPU at a small size: sound runs come out correct,
and a run whose timed path is broken underneath comes out not correct.

These call ``harness.run_cell`` directly, past ``run.require_chips``, with
each configuration cut to a few thousand rows."""
import copy
import json
import os
import subprocess
import sys
import textwrap
import time

import pytest

from chip_bench import cells, harness

ROOT = cells.BENCH_DIR.parent


#: The cells whose files are kept for a later PR, added as that PR would
#: add them: the open-loop cell (on a one-chip host its tail spreads too
#: widely to be held to a bound) and the four-chip cell (its traced run
#: was not proven on the chip).
LATER = {
    "configs": [{"name": "cop20k_A_synth.x4", "source": "kept",
                 "file": "chip_bench/configs/cop20k_A_synth.x4.json",
                 "reduced": [], "why": "four chips"}],
    "workloads": [{"name": "cop20k_A_synth.open1",
                   "config": "cop20k_A_synth", "traffic": "open1",
                   "chips": 1, "why": "open loop"},
                  {"name": "cop20k_A_synth.x4.closed1",
                   "config": "cop20k_A_synth.x4", "traffic": "closed1",
                   "chips": 4, "why": "the exchange"}],
    "end_to_end": [{"name": "latency_p95_ms", "unit": "ms",
                    "better": "lower", "bound": 0.25, "source": "host_clock",
                    "workloads": ["cop20k_A_synth.open1"]}],
    "per_layer": [{"name": "router.batch_width", "unit": "vectors",
                   "better": "higher", "source": "program_counter",
                   "layer": "Router", "moves": "latency_p95_ms",
                   "workloads": ["cop20k_A_synth.open1"]},
                  {"name": "exchange.collective_ms", "unit": "ms",
                   "better": "lower", "source": "device_trace",
                   "layer": "Exchange", "moves": "vectors_per_s",
                   "workloads": ["cop20k_A_synth.x4.closed1"]}]}


def with_later(spec: dict) -> dict:
    spec = copy.deepcopy(spec)
    for group, entries in LATER.items():
        spec[group] += copy.deepcopy(entries)
    return spec


def small_bench(tmp_path, spec=None) -> cells.Benchmark:
    """BENCHMARK.json and the cells kept for later (or ``spec``), every
    tenant of every configuration cut to about 1/100."""
    bench = cells.Benchmark(copy.deepcopy(
        spec or with_later(cells.Benchmark.load(ROOT).spec)), ROOT)
    for entry in bench.spec["configs"]:
        cfg = bench.config(entry["name"])
        for tenant in cfg["tenants"]:
            tenant["rows"] = max(tenant["rows"] // 100, 2000)
            tenant["nnz"] //= 100
            params = tenant.get("pattern_params", {})
            if "bandwidth" in params:
                params["bandwidth"] //= 100
        path = tmp_path / f"{entry['name']}.json"
        path.write_text(json.dumps(cfg))
        entry["file"] = str(path)
    return bench


@pytest.fixture(autouse=True)
def short_warm_up(monkeypatch):
    """One warm request per tenant, not the chip runs' seconds of them."""
    monkeypatch.setattr(harness, "WARM_S", 0.0)


def run(bench, cell, seconds=0.6, seed=2 ** 33 + 9):
    import jax
    return harness.run_cell(bench, cell, jax.devices(), seed, seconds,
                            False, time.perf_counter())


@pytest.mark.parametrize("cell, metric", [
    ("cop20k_A_synth.open1", "latency_p95_ms"),
    ("cop20k_A_synth.closed8", "vectors_per_s"),
    ("audikw_1_synth.closed1", "vectors_per_s"),
])
def test_a_sound_run_is_correct(tmp_path, cell, metric):
    result = run(small_bench(tmp_path), cell)
    assert result["correct"] is True
    assert result["failed"] == 0 and result["attempted"] > 0
    assert set(result["metrics"]) >= {metric, "ingest_s", "setup_s"}
    assert list(result)[-1] == "checks"
    assert result["checks"]["max_norm_err"]["value"] < 1e-5


@pytest.mark.parametrize("cell", ["cop20k_A_synth.open1",
                                  "audikw_1_synth.closed1",
                                  "cop20k_A_synth.closed8"])
def test_an_answer_altered_where_it_is_produced_is_caught(
        tmp_path, monkeypatch, cell):
    import repro.serve.router as router
    real = router.gather_b

    def altered(program, y_shards):
        y = real(program, y_shards)
        y[7] += 1.0
        return y
    monkeypatch.setattr(router, "gather_b", altered)
    result = run(small_bench(tmp_path), cell)
    assert result["correct"] is False
    assert result["checks"]["max_norm_err"]["value"] > 1e-2


def test_a_wave_that_answers_every_waiter_alike_is_caught(tmp_path,
                                                          monkeypatch):
    """Micro-batched waiters each handed the wave's first column."""
    import repro.serve.router as router
    real = router.gather_b
    widths = []

    def first_column(program, y_shards):
        y = real(program, y_shards)
        if y.ndim == 2:
            widths.append(y.shape[1])
            y[:] = y[:, :1]
        return y
    monkeypatch.setattr(router, "gather_b", first_column)
    mix = dict(cells.Benchmark.traffic("open1"), rate_per_s=400.0)
    monkeypatch.setattr(cells.Benchmark, "traffic",
                        staticmethod(lambda name: mix))
    result = run(small_bench(tmp_path), "cop20k_A_synth.open1")
    assert max(widths) > 1
    assert result["correct"] is False


def test_a_failed_request_makes_the_run_not_correct(tmp_path, monkeypatch):
    """The third request of the window raises."""
    import repro.serve.router as router
    calls = []
    real, real_drive = router.gather_b, harness.traffic.drive

    def flaky(program, y_shards):
        calls.append(1)
        if len(calls) == 3:
            raise RuntimeError("lost")
        return real(program, y_shards)

    def drive(*a, **k):
        monkeypatch.setattr(router, "gather_b", flaky)
        return real_drive(*a, **k)
    monkeypatch.setattr(harness.traffic, "drive", drive)
    result = run(small_bench(tmp_path), "audikw_1_synth.closed1")
    assert result["failed"] == 1 and result["correct"] is False


def two_tenant_bench(tmp_path, monkeypatch) -> cells.Benchmark:
    """A cell whose configuration lists two tenants, under an open mix
    that shares the requests between them by a Zipf law: new files and
    entries only, as a later cell would add them."""
    spec = with_later(cells.Benchmark.load(ROOT).spec)
    bench = cells.Benchmark(spec, ROOT)
    cfg = bench.config("cop20k_A_synth")
    band = dict(bench.config("audikw_1_synth")["tenants"][0],
                name="band", plan=None)
    cfg.update(name="pair", tenants=[cfg["tenants"][0], band])
    (tmp_path / "pair_src.json").write_text(json.dumps(cfg))
    spec["configs"].append({"name": "pair", "source": "test",
                            "file": str(tmp_path / "pair_src.json"),
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "pair.zipf", "config": "pair",
                              "traffic": "zipf", "chips": 1, "why": "t"})
    mix = dict(cells.Benchmark.traffic("open1"), rate_per_s=60.0,
               tenant_zipf=1.0)
    real = cells.Benchmark.traffic
    monkeypatch.setattr(cells.Benchmark, "traffic", staticmethod(
        lambda name: mix if name == "zipf" else real(name)))
    return small_bench(tmp_path, spec)


def test_a_cell_of_two_tenants_serves_and_checks_both(tmp_path,
                                                      monkeypatch):
    import repro.serve.router as router
    bench = two_tenant_bench(tmp_path, monkeypatch)
    served = []
    real = harness.traffic.drive

    def drive(*a, **k):
        w = real(*a, **k)
        served.extend(r.tenant for r in w.requests)
        return w
    monkeypatch.setattr(harness.traffic, "drive", drive)
    result = run(bench, "pair.zipf")
    assert result["correct"] is True
    assert 0 < served.count(1) < served.count(0)

    real_gather = router.gather_b

    def altered(program, y_shards):
        y = real_gather(program, y_shards)
        if y.shape[0] == 9430:          # only the second tenant's answers
            y[3] += 1.0
        return y
    monkeypatch.setattr(router, "gather_b", altered)
    assert run(bench, "pair.zipf")["correct"] is False


EXCHANGE_SCRIPT = textwrap.dedent("""
    import json, sys, time
    sys.path[:0] = [sys.argv[1], sys.argv[1] + "/src", sys.argv[2]]
    import jax, jax.numpy as jnp
    from chip_bench import harness
    from test_chip_bench_harness import small_bench
    from pathlib import Path
    bench = small_bench(Path(sys.argv[3]))
    out = {}
    for mode in ("sound", "no_exchange"):
        if mode == "no_exchange":
            jax.lax.all_to_all = lambda x, *a, **k: jnp.zeros_like(x)
            jax.lax.all_gather = lambda x, axis, **k: jnp.zeros(
                (4,) + x.shape, x.dtype)
        r = harness.run_cell(bench, "cop20k_A_synth.x4.closed1",
                             jax.devices(), 2 ** 33 + 11, 0.5, False,
                             time.perf_counter())
        out[mode] = r["correct"]
    print(json.dumps(out))
""")


def test_the_exchange_left_out_is_caught(tmp_path):
    """On four virtual CPU devices: the sharded cell is correct, and not
    correct once the collectives that carry x between shards return
    zeros."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run(
        [sys.executable, "-c", EXCHANGE_SCRIPT, str(ROOT),
         str(ROOT / "tests" / "chip_bench"), str(tmp_path)],
        env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert json.loads(proc.stdout.strip().splitlines()[-1]) == {
        "sound": True, "no_exchange": False}


def _bench_cli(cwd, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "chip_bench/run.py", "--workload",
         "cop20k_A_synth.closed8", "--seed", "1", "--seconds", "1", *args],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_without_a_tpu_the_command_fails_and_prints_no_result():
    proc = _bench_cli(ROOT)
    assert proc.returncode != 0
    assert proc.stdout == ""
    assert "no TPU" in proc.stderr


def test_with_only_the_benchmarks_files_the_command_fails(tmp_path):
    import shutil
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for p in json.loads((ROOT / "BENCHMARK.json").read_text())["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    proc = _bench_cli(tmp_path)
    assert proc.returncode != 0
    assert proc.stdout == ""
