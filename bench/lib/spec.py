"""Find everything one cell needs, by the names in ``BENCHMARK.json``.

Nothing here names a cell, a configuration, a traffic mix or a metric:
each lives in a file of its own, found by its name.

    bench/cells/<workload>.json     engine settings, load, limits
    bench/configs/<config>.json     the model as published, and as run
    bench/traffic/<traffic>.json    parameters of the one generator
    bench/metrics/<metric>.py       a reader: ``read(run) -> float | None``
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib
from typing import Any, Callable, Dict, List, Optional

BENCH_DIR = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent


@dataclasses.dataclass
class Metric:
    name: str
    unit: str
    read: Callable[[Any], Optional[float]]


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]             # bench/configs/<config>.json
    traffic: Dict[str, Any]            # bench/traffic/<traffic>.json
    cell: Dict[str, Any]               # bench/cells/<name>.json
    end_to_end: List[Metric]
    per_layer: List[Metric]


def _load_json(path: pathlib.Path) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def load_reader(name: str, bench_dir: pathlib.Path = BENCH_DIR):
    """``bench/metrics/<name>.py``'s ``read`` function."""
    path = bench_dir / "metrics" / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"metric {name!r} has no reader at {path}")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _applies(metric: Dict[str, Any], workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def load_cell(workload: str, *, root: pathlib.Path = ROOT) -> Cell:
    """Resolve ``workload`` against ``<root>/BENCHMARK.json``."""
    bench = _load_json(root / "BENCHMARK.json")
    bench_dir = root / "bench"
    wl = {w["name"]: w for w in bench["workloads"]}
    if workload not in wl:
        raise KeyError(f"unknown workload {workload!r}; known: "
                       f"{sorted(wl)}")
    w = wl[workload]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[w["config"]]

    def metrics(kind):
        return [Metric(m["name"], m["unit"], load_reader(m["name"], bench_dir))
                for m in bench[kind] if _applies(m, workload)]

    return Cell(
        name=workload, chips=int(w["chips"]),
        config=_load_json(root / cfg_entry["file"]),
        traffic=_load_json(bench_dir / "traffic" / f"{w['traffic']}.json"),
        cell=_load_json(bench_dir / "cells" / f"{workload}.json"),
        end_to_end=metrics("end_to_end"), per_layer=metrics("per_layer"))
