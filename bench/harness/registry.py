"""Find a cell's parts by the names ``BENCHMARK.json`` gives them.

* a configuration: ``configs[].file``, a JSON file of sizes that names
  its ``driver`` (``bench/drivers/<driver>.py``: how one job runs
  through the program) and its ``reference``
  (``bench/reference/<reference>.py``: the plain reference, the count
  of simulated operations and the comparison);
* a traffic mix: ``bench/traffic/<traffic>.json``;
* a metric: ``bench/metrics/<name>.py``, whose ``read(window)``
  returns the metric's value or ``None`` where it finds nothing to
  read.
"""
from __future__ import annotations

import importlib.util
import json
import sys
from dataclasses import dataclass
from pathlib import Path
from types import ModuleType
from typing import Dict, List

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


def load_module(path: Path) -> ModuleType:
    """Import a file of the benchmark by its path."""
    if not path.is_file():
        raise FileNotFoundError(f"no such benchmark file: {path}")
    name = "bench_" + "_".join(path.relative_to(BENCH).with_suffix("")
                               .parts).replace("-", "_")
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def read_json(path: Path) -> Dict:
    with open(path) as f:
        return json.load(f)


@dataclass
class Cell:
    name: str
    chips: int
    config: Dict
    traffic: Dict
    driver: ModuleType
    reference: ModuleType
    #: the metric entries of BENCHMARK.json this cell reports, in order
    end_to_end: List[Dict]
    per_layer: List[Dict]


def reports(metric: Dict, cell: str) -> bool:
    """Does ``cell`` report ``metric`` (no ``workloads`` key: every
    cell does)?"""
    return cell in metric.get("workloads", [cell])


def find_cell(name: str, bench: Dict = None) -> Cell:
    """The cell called ``name``, with its parts loaded."""
    bench = bench if bench is not None else read_json(ROOT /
                                                      "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; have {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = read_json(ROOT / configs[w["config"]]["file"])
    traffic = read_json(BENCH / "traffic" / f"{w['traffic']}.json")
    return Cell(
        name=name, chips=w["chips"], config=config, traffic=traffic,
        driver=load_module(BENCH / "drivers" / f"{config['driver']}.py"),
        reference=load_module(BENCH / "reference"
                              / f"{config['reference']}.py"),
        end_to_end=[m for m in bench["end_to_end"] if reports(m, name)],
        per_layer=[m for m in bench["per_layer"] if reports(m, name)])


def metric_reader(name: str) -> ModuleType:
    return load_module(BENCH / "metrics" / f"{name}.py")
