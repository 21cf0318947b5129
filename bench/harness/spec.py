"""Find a cell's files by name: nothing here is specific to one cell.

``BENCHMARK.json`` (repo root) names each workload's configuration and
traffic mix.  The files are

* ``bench/configs/<config>.json``   sizes as run, source and deployment;
* ``bench/reference/<module>.py``   the configuration's plain reference;
* ``bench/traffic/<traffic>.json``  the mix's parameters;
* ``bench/checks/<workload>.json``  the limits that decide ``correct``;
* ``bench/metrics/<metric>.py``     one reader per per-layer metric.
"""
from __future__ import annotations

import importlib.util
import json
import pathlib
from dataclasses import dataclass, field
from typing import Any, Dict, List

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


def load_json(path: pathlib.Path) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def load_module(path: pathlib.Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Cell:
    """One workload with every file it names, loaded."""

    name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    check: Dict[str, Any]
    end_to_end: List[Dict[str, Any]] = field(default_factory=list)
    per_layer: List[Dict[str, Any]] = field(default_factory=list)

    def reference(self):
        mod = self.config["deployment"]["reference"]
        return load_module(BENCH / "reference" / f"{mod}.py",
                           f"bench_reference_{mod}")


def _applies(metric: Dict[str, Any], workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def load_cell(workload: str, benchmark: Dict[str, Any] = None) -> Cell:
    """The cell ``workload`` of ``BENCHMARK.json`` (or of ``benchmark``)."""
    bm = benchmark if benchmark is not None else load_json(
        ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in bm["workloads"]}
    if workload not in cells:
        raise KeyError(f"unknown workload {workload!r}; "
                       f"known: {sorted(cells)}")
    w = cells[workload]
    return Cell(
        name=workload, chips=int(w["chips"]),
        config=load_json(BENCH / "configs" / f"{w['config']}.json"),
        traffic=load_json(BENCH / "traffic" / f"{w['traffic']}.json"),
        check=load_json(BENCH / "checks" / f"{workload}.json"),
        end_to_end=[m for m in bm["end_to_end"] if _applies(m, workload)],
        per_layer=[m for m in bm["per_layer"] if _applies(m, workload)])


def metric_reader(name: str):
    """``bench/metrics/<name>.py``'s ``read(ctx)``."""
    mod = load_module(BENCH / "metrics" / f"{name}.py",
                      "bench_metric_" + name.replace(".", "_")
                      .replace("-", "_"))
    return mod.read
