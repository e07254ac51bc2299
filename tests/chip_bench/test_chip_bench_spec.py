"""BENCHMARK.json names only what exists, and each name is found by the
harness: configurations, traffic mixes and metric readers."""
import json
import re

import pytest

from chip_bench import cells

ROOT = cells.BENCH_DIR.parent
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_top_level_keys():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert 1 <= SPEC["run_seconds"] <= 51


def test_every_name_is_well_formed_and_unique():
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in SPEC[group]]
        assert len(set(names)) == len(names), group
        assert all(NAME.match(n) for n in names), names
    metric_names = [m["name"] for g in ("end_to_end", "per_layer")
                    for m in SPEC[g]]
    assert len(set(metric_names)) == len(metric_names)


@pytest.mark.parametrize("entry", SPEC["configs"], ids=lambda e: e["name"])
def test_each_configuration_file_is_found(entry):
    assert any(entry["file"].startswith(p + "/") for p in SPEC["paths"])
    cfg = json.loads((ROOT / entry["file"]).read_text())
    assert cfg["name"] == entry["name"]
    assert cfg["source"] == entry["source"]
    assert cfg["reduced"] == entry["reduced"]
    assert cfg["tenants"]
    for tenant in cfg["tenants"]:
        assert (cells.BENCH_DIR / "patterns" /
                f"{tenant['pattern']}.py").exists()


@pytest.mark.parametrize("cell", SPEC["workloads"], ids=lambda e: e["name"])
def test_each_cell_finds_its_mix_and_reports_enough(cell):
    bench = cells.Benchmark(SPEC, ROOT)
    assert cell["chips"] in (1, 4)
    assert bench.config(cell["config"])["chips"] == cell["chips"]
    mix = bench.traffic(cell["traffic"])
    assert mix["loop"] in ("open", "closed")
    if mix["loop"] == "open":
        assert (cells.BENCH_DIR / "schedules" /
                f"{mix['schedule']}.py").exists()
    e2e = [m["name"] for m in bench.metrics(cell["name"], traced=False)]
    assert "setup_s" in e2e and len(e2e) >= 2
    layer = bench.metrics(cell["name"], traced=True)
    assert layer
    for m in layer:
        assert m["moves"] in e2e


@pytest.mark.parametrize("metric", SPEC["end_to_end"] + SPEC["per_layer"],
                         ids=lambda m: m["name"])
def test_each_metric_has_a_reader(metric):
    assert callable(cells.reader(metric["name"]))
    assert metric["better"] in ("lower", "higher")
    if "bound" in metric:
        assert 0.01 <= metric["bound"] <= 0.25


@pytest.mark.parametrize("path", sorted((cells.BENCH_DIR / "traffic").glob(
    "*.json")), ids=lambda p: p.stem)
def test_each_mix_file_names_what_the_generator_reads(path):
    mix = json.loads(path.read_text())
    assert mix["loop"] in ("open", "closed")
    assert int(mix["batch"]) >= 1 and int(mix["pool"]) >= 1
    if mix["loop"] == "open":
        assert (cells.BENCH_DIR / "schedules" /
                f"{mix['schedule']}.py").exists()
        assert int(mix["threads"]) >= 1
    else:
        assert int(mix["clients"]) >= 1


@pytest.mark.parametrize("path", sorted((cells.BENCH_DIR / "metrics").glob(
    "[!_]*.py")), ids=lambda p: p.stem)
def test_each_reader_file_loads(path):
    assert callable(cells.reader(path.stem))


@pytest.mark.parametrize("path", sorted((cells.BENCH_DIR / "configs").glob(
    "*.json")), ids=lambda p: p.stem)
def test_each_configuration_file_states_its_deployment(path):
    cfg = json.loads(path.read_text())
    assert cfg["name"] == path.stem
    assert cfg["chips"] in (1, 4) and cfg["tenants"]
    assert cfg["value_dtype"] == "float32"
    assert cfg["reference_dtype"] == "float64"
    assert 0 < cfg["limits"]["max_norm_err"] < 1e-3
    for tenant in cfg["tenants"]:
        assert (cells.BENCH_DIR / "patterns" /
                f"{tenant['pattern']}.py").exists()
        assert tenant["stored_nnz"] >= tenant["nnz"]
