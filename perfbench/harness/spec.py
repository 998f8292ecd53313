"""``BENCHMARK.json`` and the files it names.

A cell (``workloads`` entry) names a configuration and a traffic mix.
Each is found by name: ``configs/<config>.json`` and
``traffic/<traffic>.json`` under this directory, and every metric by
``metrics/<metric name>.py``. Adding a cell, a configuration, a traffic
mix or a metric therefore means adding files and entries, never editing
one that exists.
"""
from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List, Optional

BENCH_DIR = Path(__file__).resolve().parents[1]
CONFIG_DIR = BENCH_DIR / "configs"
TRAFFIC_DIR = BENCH_DIR / "traffic"
METRIC_DIR = BENCH_DIR / "metrics"


class SpecError(RuntimeError):
    """The benchmark definition is incomplete or inconsistent."""


@dataclass
class Metric:
    name: str
    unit: str
    better: str
    source: str
    end_to_end: bool
    bound: Optional[float] = None
    moves: Optional[str] = None
    layer: Optional[str] = None
    workloads: Optional[List[str]] = None

    def applies_to(self, cell: str) -> bool:
        return self.workloads is None or cell in self.workloads


@dataclass
class Cell:
    name: str
    config: str
    traffic: str
    chips: int
    why: str
    config_data: Dict = field(default_factory=dict)
    traffic_data: Dict = field(default_factory=dict)
    end_to_end: List[Metric] = field(default_factory=list)
    per_layer: List[Metric] = field(default_factory=list)


def load_benchmark(root: Path) -> Dict:
    path = Path(root) / "BENCHMARK.json"
    if not path.is_file():
        raise SpecError(f"no BENCHMARK.json in {root}")
    with open(path) as f:
        return json.load(f)


def _metric(entry: Dict, end_to_end: bool) -> Metric:
    return Metric(name=entry["name"], unit=entry["unit"],
                  better=entry["better"], source=entry["source"],
                  end_to_end=end_to_end, bound=entry.get("bound"),
                  moves=entry.get("moves"), layer=entry.get("layer"),
                  workloads=entry.get("workloads"))


def metric_path(name: str) -> Path:
    return METRIC_DIR / f"{name}.py"


def config_path(name: str) -> Path:
    return CONFIG_DIR / f"{name}.json"


def traffic_path(name: str) -> Path:
    return TRAFFIC_DIR / f"{name}.json"


def read_json(path: Path) -> Dict:
    if not path.is_file():
        raise SpecError(f"missing file {path}")
    with open(path) as f:
        return json.load(f)


def served(config: Dict) -> Dict:
    """A configuration as the program runs it: the file's top level holds
    the published values, and ``departures`` what the program serves
    differently (its architecture, not a cut of size)."""
    return {**config, **config.get("departures", {})}


def resolve_cell(bench: Dict, name: str) -> Cell:
    """The cell ``name`` with its configuration, traffic and the metrics
    it reports, every one of them found by name on disk."""
    cells = {w["name"]: w for w in bench.get("workloads", [])}
    if name not in cells:
        raise SpecError(f"no workload {name!r}; have {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench.get("configs", [])}
    if w["config"] not in configs:
        raise SpecError(f"workload {name} names unknown config "
                        f"{w['config']!r}")
    want = config_path(w["config"]).relative_to(BENCH_DIR.parent)
    if Path(configs[w["config"]]["file"]) != want:
        raise SpecError(f"config {w['config']} must live at {want}")
    cell = Cell(name=name, config=w["config"], traffic=w["traffic"],
                chips=int(w["chips"]), why=w["why"],
                config_data=served(read_json(config_path(w["config"]))),
                traffic_data=read_json(traffic_path(w["traffic"])))
    # the end-to-end metrics a workload reports, and the per-layer ones
    # that move one of them there
    cell.end_to_end = [_metric(m, True) for m in bench["end_to_end"]
                       if _metric(m, True).applies_to(name)]
    reported = {m.name for m in cell.end_to_end}
    for m in bench.get("per_layer", []):
        pm = _metric(m, False)
        if pm.workloads is None:
            if pm.moves in reported:
                cell.per_layer.append(pm)
        elif name in pm.workloads:
            cell.per_layer.append(pm)
    for m in cell.end_to_end + cell.per_layer:
        if not metric_path(m.name).is_file():
            raise SpecError(f"metric {m.name} has no reader "
                            f"{metric_path(m.name)}")
    return cell


def load_reader(name: str):
    """The ``read`` function of ``metrics/<name>.py``: it takes the run
    record and returns a number, or None where it finds nothing."""
    path = metric_path(name)
    spec = importlib.util.spec_from_file_location(
        "perfbench_metric_" + name.replace(".", "_").replace("-", "_"),
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
