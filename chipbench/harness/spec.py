"""Find a cell's parts by name: ``BENCHMARK.json`` names the cells, and each
configuration, traffic mix, metric reader, correctness limit and
architecture module is a file of its own under ``chipbench/``, so a later
cell, metric or model adds files and edits none.  A configuration file names
its architecture (``"architecture"``), whose module ``harness.arch`` loads
from the same checkout."""

from __future__ import annotations

import importlib.util
import json
import pathlib
from types import ModuleType
from typing import List, Optional

ROOT = pathlib.Path(__file__).resolve().parents[2]
# the key under which ``Bench.config`` notes the directory of the checkout's
# architecture modules (``harness.arch``)
ARCH_DIR_KEY = "_arch_dir"


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
        """The configuration file's keys, and where its architecture module
        is found: this checkout's ``chipbench/arch/``."""
        for c in self.spec["configs"]:
            if c["name"] == name:
                cfg = json.loads((self.root / c["file"]).read_text())
                cfg[ARCH_DIR_KEY] = str(self.dir / "arch")
                return cfg
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


def load_module(path: pathlib.Path, kind: str, name: str) -> ModuleType:
    """The Python file ``path`` imported as a module of its own, named
    ``chipbench_<kind>_<name>``; a missing file is named in the error."""
    path = pathlib.Path(path)
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{kind}_" + name.replace(".", "_").replace("-", "_"),
        path)
    if spec is None or not path.is_file():
        raise FileNotFoundError(f"no {kind} {name!r} at {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reader(directory: pathlib.Path, metric: str) -> ModuleType:
    """``<directory>/<metric>.py``: a module whose ``read(run)`` returns the
    metric's value, or None where the run holds nothing to read."""
    return load_module(pathlib.Path(directory) / f"{metric}.py", "metric",
                       metric)


def read_metric(mod: ModuleType, run) -> Optional[float]:
    value = mod.read(run)
    return None if value is None else float(value)
