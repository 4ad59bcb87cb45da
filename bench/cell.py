"""Resolve a cell of BENCHMARK.json into its parts, each found by name:
the configuration (`configs/<config>.json`, the file BENCHMARK.json
names), the traffic (`traffic/<traffic>.json`) and the per-layer metric
readers (`metrics/<metric>.py`).  A new configuration, traffic or metric
is a new file plus a new entry in BENCHMARK.json; nothing here changes."""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional

REPO = Path(__file__).resolve().parents[1]
BENCH_DIR = "bench"


class CellError(ValueError):
    pass


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]
    root: Path

    def reader(self, metric: str) -> Callable[[Any], Optional[float]]:
        """The `read(ctx)` function of `metrics/<metric>.py`."""
        path = self.root / BENCH_DIR / "metrics" / f"{metric}.py"
        spec = importlib.util.spec_from_file_location(
            f"bench_metric_{metric.replace('.', '_').replace('-', '_')}",
            path)
        if spec is None or not path.is_file():
            raise CellError(f"no reader for metric {metric!r} at {path}")
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.read


def _load_json(path: Path) -> Dict[str, Any]:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise CellError(f"missing file {path}") from None


def _applies(metric: Dict[str, Any], cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def resolve(workload: str, root: Path = REPO) -> Cell:
    spec = _load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise CellError(f"unknown workload {workload!r}; known: "
                        f"{sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in spec["configs"]}
    if w["config"] not in configs:
        raise CellError(f"cell {workload!r} names unknown config "
                        f"{w['config']!r}")
    config = _load_json(root / configs[w["config"]]["file"])
    traffic = _load_json(root / BENCH_DIR / "traffic"
                         / f"{w['traffic']}.json")
    return Cell(name=workload, chips=int(w["chips"]), config=config,
                traffic=traffic,
                end_to_end=[m for m in spec["end_to_end"]
                            if _applies(m, workload)],
                per_layer=[m for m in spec["per_layer"]
                           if _applies(m, workload)],
                root=root)
