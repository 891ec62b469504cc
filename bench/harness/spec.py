"""Finds what a cell is made of, by the names that BENCHMARK.json gives.

A cell names a configuration and a traffic mix; the configuration names its
driver.  Each is a file of its own, found by name:

  bench/configs/<config>.json     sizes of the model as it is run
  bench/traffic/<traffic>.json    parameters of the one traffic generator
  bench/drivers/<driver>.py       the path that drives the program
  bench/metrics/<metric>.py       reader of one per-layer metric

so a later change adds a configuration, a mix or a metric by adding files.
"""

from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path
from typing import Any, Callable, Dict, List

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


@dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]


def load_benchmark(root: Path = ROOT) -> Dict[str, Any]:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def _load_json(path: Path) -> Dict[str, Any]:
    if not path.is_file():
        raise FileNotFoundError(f"{path} is missing")
    with open(path) as f:
        return json.load(f)


def _applies(metric: Dict[str, Any], cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def resolve(name: str, bench: Dict[str, Any] = None,
            root: Path = ROOT) -> Cell:
    """The cell called ``name``, with its configuration and traffic loaded
    and the metrics it reports picked out."""
    bench = bench if bench is not None else load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    w = cells[name]
    cfgs = {c["name"]: c for c in bench["configs"]}
    config = _load_json(root / cfgs[w["config"]]["file"])
    traffic = _load_json(root / "bench" / "traffic" / f"{w['traffic']}.json")
    e2e = [m for m in bench["end_to_end"] if _applies(m, name)]
    per_layer = [m for m in bench["per_layer"] if _applies(m, name)]
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic, end_to_end=e2e, per_layer=per_layer)


def _load_module(path: Path, label: str):
    if not path.is_file():
        raise FileNotFoundError(f"{path} is missing")
    spec = importlib.util.spec_from_file_location(label, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def driver(config: Dict[str, Any], bench_dir: Path = BENCH):
    """The driver module the configuration names (``run(cell, seed,
    seconds, trace)``)."""
    return _load_module(bench_dir / "drivers" / f"{config['driver']}.py",
                        f"bench_driver_{config['driver']}")


def metric_reader(name: str, bench_dir: Path = BENCH) -> Callable:
    """``read(ctx) -> float | None`` of the per-layer metric ``name``."""
    mod = _load_module(bench_dir / "metrics" / f"{name}.py",
                       "bench_metric_" + name.replace(".", "_"))
    return mod.read
