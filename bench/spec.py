"""Finds everything a cell needs, by name, in files of its own.

* ``BENCHMARK.json`` at the checkout root names the cells (``workloads``),
  their configuration and traffic, and the metrics;
* a configuration is the JSON file its ``configs`` entry names;
* a traffic mix is ``traffic/<mix>.json``, whose ``generator`` names
  ``traffic/gen_<generator>.py``;
* a metric is ``metrics/<metric>.py`` with ``read(run) -> float | None``;
* a cell's limits for ``correct`` are ``limits/<cell>.json``;
* peaks are ``peaks.json``, keyed by the device kind JAX reports.

Adding a configuration, a mix or a metric adds files and entries; no
file here changes.
"""
from __future__ import annotations

import importlib.util
import json
import pathlib
import sys
from types import ModuleType
from typing import Optional

__all__ = ["Bench", "BENCH_DIR"]

BENCH_DIR = pathlib.Path(__file__).resolve().parent


def _load(path: pathlib.Path) -> ModuleType:
    name = "bench_" + path.stem.replace(".", "_").replace("-", "_")
    if name in sys.modules and sys.modules[name].__file__ == str(path):
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


class Bench:
    """The benchmark's files under ``bench_dir``, read through ``spec``
    (``BENCHMARK.json`` of ``root`` unless given)."""

    def __init__(self, root: pathlib.Path, spec: Optional[dict] = None,
                 bench_dir: Optional[pathlib.Path] = None):
        self.root = pathlib.Path(root)
        self.dir = pathlib.Path(bench_dir or BENCH_DIR)
        if spec is None:
            spec = json.loads((self.root / "BENCHMARK.json").read_text())
        self.spec = spec

    def workload(self, name: str) -> dict:
        for w in self.spec["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")

    def config(self, name: str) -> dict:
        for c in self.spec["configs"]:
            if c["name"] == name:
                return json.loads((self.root / c["file"]).read_text())
        raise KeyError(f"no configuration {name!r} in BENCHMARK.json")

    def traffic(self, name: str) -> dict:
        return json.loads((self.dir / "traffic" / f"{name}.json").read_text())

    def generator(self, name: str) -> ModuleType:
        return _load(self.dir / "traffic" / f"gen_{name}.py")

    def metric(self, name: str) -> ModuleType:
        return _load(self.dir / "metrics" / f"{name}.py")

    def limits(self, workload: str) -> dict:
        path = self.dir / "limits" / f"{workload}.json"
        return json.loads(path.read_text()) if path.exists() else {}

    def peaks(self, device_kind: str) -> dict:
        table = json.loads((self.dir / "peaks.json").read_text())
        if device_kind not in table["devices"]:
            raise KeyError(f"device kind {device_kind!r} is not in the peak "
                           f"table {sorted(table['devices'])}")
        return table["devices"][device_kind]

    def metrics_for(self, workload: str, traced: bool) -> list:
        """The metric entries this cell reports in a run of this kind."""
        group = self.spec["per_layer" if traced else "end_to_end"]
        return [m for m in group
                if workload in m.get("workloads", [workload])]
