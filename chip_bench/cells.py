"""Find a cell, its configuration, its traffic mix and its metrics by name.

``BENCHMARK.json`` names everything; each configuration, mix and metric is
a file of its own under this directory, so a new cell is new files and a
new ``workloads`` entry, and no file that is here changes.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path

__all__ = ["BENCH_DIR", "Benchmark"]

BENCH_DIR = Path(__file__).resolve().parent


class Benchmark:
    """``BENCHMARK.json`` and the files it names."""

    def __init__(self, spec: dict, root: Path):
        self.spec = spec
        self.root = root

    @classmethod
    def load(cls, root: Path = BENCH_DIR.parent) -> "Benchmark":
        return cls(json.loads((root / "BENCHMARK.json").read_text()), root)

    @staticmethod
    def _named(entries, name: str, what: str) -> dict:
        for e in entries:
            if e["name"] == name:
                return e
        raise KeyError(f"no {what} named {name!r}; known: "
                       f"{[e['name'] for e in entries]}")

    def cell(self, name: str) -> dict:
        return self._named(self.spec["workloads"], name, "workload")

    def config(self, name: str) -> dict:
        entry = self._named(self.spec["configs"], name, "configuration")
        return json.loads((self.root / entry["file"]).read_text())

    @staticmethod
    def traffic(name: str) -> dict:
        return json.loads((BENCH_DIR / "traffic" / f"{name}.json").read_text())

    def metrics(self, cell: str, traced: bool) -> list:
        """The metric entries a run of ``cell`` reports: its end-to-end
        metrics untraced, its per-layer metrics traced."""
        kind = "per_layer" if traced else "end_to_end"
        return [m for m in self.spec[kind]
                if cell in m.get("workloads", [cell])]


def reader(metric: str):
    """The ``read(run)`` function of ``metrics/<metric>.py``."""
    path = BENCH_DIR / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        f"chip_bench.metrics.{metric}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
