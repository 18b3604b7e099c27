"""Find a cell's parts by name: ``BENCHMARK.json`` names the cells, and each
configuration, traffic mix, metric reader and correctness limit is a file of
its own under ``chipbench/``, so a later cell adds files and edits none."""

from __future__ import annotations

import importlib.util
import json
import pathlib
from types import ModuleType
from typing import List, Optional

ROOT = pathlib.Path(__file__).resolve().parents[2]


class Bench:
    """``BENCHMARK.json`` and the files it names, under one checkout root."""

    def __init__(self, root: pathlib.Path = ROOT):
        self.root = pathlib.Path(root)
        self.spec = json.loads((self.root / "BENCHMARK.json").read_text())
        self.dir = self.root / "chipbench"

    def cell(self, name: str) -> dict:
        for w in self.spec["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                       f"{[w['name'] for w in self.spec['workloads']]}")

    def config(self, name: str) -> dict:
        for c in self.spec["configs"]:
            if c["name"] == name:
                return json.loads((self.root / c["file"]).read_text())
        raise KeyError(f"no config {name!r} in BENCHMARK.json")

    def traffic(self, name: str) -> dict:
        return json.loads((self.dir / "traffic" / f"{name}.json").read_text())

    def limits(self, cell: str) -> dict:
        return json.loads((self.dir / "limits" / f"{cell}.json").read_text())

    def metrics(self, cell: str, trace: bool) -> List[dict]:
        """The metrics a run of ``cell`` reports: end-to-end ones untraced,
        per-layer ones traced; a metric with a ``workloads`` key only in the
        cells it lists."""
        group = self.spec["per_layer"] if trace else self.spec["end_to_end"]
        return [m for m in group if cell in m.get("workloads", [cell])]

    def reader(self, metric: str) -> ModuleType:
        return load_reader(self.dir / "metrics", metric)


def load_reader(directory: pathlib.Path, metric: str) -> ModuleType:
    """``<directory>/<metric>.py``: a module whose ``read(run)`` returns the
    metric's value, or None where the run holds nothing to read."""
    path = pathlib.Path(directory) / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        "chipbench_metric_" + metric.replace(".", "_").replace("-", "_"),
        path)
    if spec is None or not path.is_file():
        raise FileNotFoundError(f"no reader for metric {metric!r} at {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def read_metric(mod: ModuleType, run) -> Optional[float]:
    value = mod.read(run)
    return None if value is None else float(value)
