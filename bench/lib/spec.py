"""Find everything one cell needs, by the names in ``BENCHMARK.json``.

Nothing here names a cell, a configuration, a traffic mix or a metric:
each lives in a file of its own, found by its name.

    bench/cells/<workload>.json     engine settings, load, limits
    bench/configs/<config>.json     the model as published, and as run
    <its "reference">.py            its architecture: layout, work and
                                    plain reference (``bench/arch/``)
    bench/traffic/<traffic>.json    parameters of the one generator
    bench/metrics/<metric>.py       a reader: ``read(run) -> float | None``
"""
from __future__ import annotations

import dataclasses
import functools
import importlib.util
import json
import pathlib
import re
import sys
from typing import Any, Callable, Dict, List, Optional

BENCH_DIR = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent
#: what an architecture module exports (each module under ``bench/arch/``
#: says what each is)
ARCH_NAMES = ("Dims", "dims", "program_config", "shapes", "std", "logits",
              "dispatch_work")


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
    arch: Any                          # the module its "reference" names
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


def load_arch(path) -> Any:
    """The architecture module at ``path``, loaded once per file, with
    every name of ``ARCH_NAMES``."""
    return _load_arch(pathlib.Path(path).resolve())


@functools.cache
def _load_arch(path: pathlib.Path) -> Any:
    if not path.is_file():
        raise FileNotFoundError(f"no architecture module at {path}")
    name = "bench_arch_" + re.sub(r"\W", "_", str(path.with_suffix("")))
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    # registered first: a dataclass looks its module up as it is made
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    missing = [n for n in ARCH_NAMES if not hasattr(mod, n)]
    if missing:
        raise AttributeError(f"architecture module {path} lacks {missing}")
    return mod


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

    config = _load_json(root / cfg_entry["file"])
    return Cell(
        name=workload, chips=int(w["chips"]), config=config,
        arch=load_arch(root / config["reference"]),
        traffic=_load_json(bench_dir / "traffic" / f"{w['traffic']}.json"),
        cell=_load_json(bench_dir / "cells" / f"{workload}.json"),
        end_to_end=metrics("end_to_end"), per_layer=metrics("per_layer"))
