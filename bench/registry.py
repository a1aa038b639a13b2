"""Finds every part of a cell by name, from files alone.

* ``BENCHMARK.json`` at the repository root lists the cells, the
  configurations and the metrics;
* a configuration ``<name>`` is ``bench/configs/<name>.json`` (its
  ``file`` entry in ``BENCHMARK.json``);
* a traffic mix ``<name>`` is the data file ``bench/traffic/<name>.json``;
  its ``driver`` key names ``bench/drivers/<driver>.py``, which turns the
  mix into calls of the program;
* a metric ``<name>`` is read by ``bench/metrics/<name>.py``.

Adding a cell, a configuration, a mix or a metric adds files and entries;
no file here changes.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def _load_module(path: Path, name: str):
    if not path.is_file():
        raise FileNotFoundError(f"no file {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    config: str
    traffic: str
    chips: int


@dataclasses.dataclass
class Registry:
    spec: dict
    bench: Path = BENCH

    @classmethod
    def load(cls, root: Path = ROOT, bench: Path = BENCH) -> "Registry":
        with open(Path(root) / "BENCHMARK.json") as f:
            return cls(json.load(f), Path(bench))

    def cell(self, name: str) -> Cell:
        for w in self.spec["workloads"]:
            if w["name"] == name:
                return Cell(w["name"], w["config"], w["traffic"],
                            int(w["chips"]))
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")

    def config(self, name: str) -> dict:
        for c in self.spec["configs"]:
            if c["name"] == name:
                with open(self.bench.parent / c["file"]) as f:
                    return json.load(f)
        raise KeyError(f"no config {name!r} in BENCHMARK.json")

    def traffic(self, name: str) -> dict:
        with open(self.bench / "traffic" / f"{name}.json") as f:
            return json.load(f)

    def driver(self, name: str):
        """The ``Driver`` class of ``bench/drivers/<name>.py``."""
        return _load_module(self.bench / "drivers" / f"{name}.py",
                            f"bench_driver_{name}").Driver

    def reader(self, metric: str):
        """The ``read(ctx)`` function of ``bench/metrics/<metric>.py``."""
        return _load_module(self.bench / "metrics" / f"{metric}.py",
                            f"bench_metric_{metric}").read

    def metrics(self, cell: str, traced: bool) -> list[dict]:
        """The metrics a run of ``cell`` reports: end-to-end ones untraced,
        per-layer ones traced; a metric with a ``workloads`` list belongs
        to those cells only."""
        group = self.spec["per_layer" if traced else "end_to_end"]
        return [m for m in group
                if "workloads" not in m or cell in m["workloads"]]
