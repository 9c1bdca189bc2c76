"""Find a cell's pieces by name.

``BENCHMARK.json`` names every configuration, traffic mix, cell and metric.
Each piece is a file of its own under ``bench/``, found by that name:

  configs/<config>.json        the model and its deployment
  traffic/<traffic>.json       the traffic mix; its ``kind`` names
  gen/<kind>.py                the generator that reads it
  metrics/<metric>.py          one reader per per-layer metric
  reference/<config>.py        the plain reference of the configuration

so a later cell, mix or metric is added as new files and entries only.
"""
from __future__ import annotations

import importlib.util
import json
import sys
from dataclasses import dataclass
from pathlib import Path
from typing import List

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict
    traffic_name: str
    traffic: dict
    end_to_end: List[dict]     # the cell's end-to-end metrics
    per_layer: List[dict]      # the cell's per-layer metrics
    bench_dir: Path

    def generator(self):
        kind = self.traffic["kind"]
        return load_module(self.bench_dir / "gen" / f"{kind}.py",
                           f"bench_gen_{kind}")

    def reference(self):
        return load_module(
            self.bench_dir / "reference" / f"{self.config_name}.py",
            "bench_ref_" + "".join(c if c.isalnum() else "_"
                                   for c in self.config_name))

    def reader(self, metric: str):
        return load_module(self.bench_dir / "metrics" / f"{metric}.py",
                           "bench_metric_" + metric.replace(".", "_"))


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: Path = ROOT, bench_dir: Path = BENCH) -> Cell:
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    conf = {c["name"]: c for c in spec["configs"]}[w["config"]]
    config = json.loads((root / conf["file"]).read_text())
    traffic = json.loads(
        (bench_dir / "traffic" / f"{w['traffic']}.json").read_text())
    e2e = [m for m in spec["end_to_end"] if _applies(m, name)]
    # a per-layer metric without a workloads list reports in every cell
    # that reports the end-to-end metric it moves
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"]
                 if _applies(m, name) and m["moves"] in e2e_names]
    return Cell(name=name, chips=int(w["chips"]), config_name=w["config"],
                config=config, traffic_name=w["traffic"], traffic=traffic,
                end_to_end=e2e, per_layer=per_layer, bench_dir=bench_dir)
