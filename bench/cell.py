"""Resolve a cell of BENCHMARK.json into its parts, each found by name:
the configuration (`configs/<config>.json`, the file BENCHMARK.json
names), the traffic (`traffic/<traffic>.json`) and the per-layer metric
readers (`metrics/<metric>.py`).  A new traffic or metric is its file and
its entry in BENCHMARK.json.  A new configuration is its file and its
entries; its `network` names a paper CNN (`name`, `batch_size`,
`processing`) or an LM block lowered from a `repro.configs` registry id
(`name`, `lm`, `shape`, `overrides`), and an LM configuration also brings
its reference network file, `reference/networks/<network name>.py`,
whose `task(config)` builds the reference's workloads.  Nothing here
changes."""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import re
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional

REPO = Path(__file__).resolve().parents[1]
BENCH_DIR = "bench"
NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}")
LM_SHAPE = ("seq_len", "global_batch", "kind")
LM_KINDS = ("train", "prefill", "decode")


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


def _lm_network(config: Dict[str, Any], root: Path) -> Dict[str, Any]:
    """An LM configuration's `network`, checked, with `reference_file`:
    the path of its reference network file under `root`, which the
    reference and its worker processes load."""
    net = config["network"]
    missing = [k for k in ("name", "lm", "shape") if k not in net]
    if missing:
        raise CellError(f"LM network lacks {missing}: {net}")
    if not isinstance(net["name"], str) or not NAME.fullmatch(net["name"]):
        raise CellError(f"LM network name {net['name']!r} is not a name")
    shape = net["shape"]
    if not isinstance(shape, dict) or sorted(shape) != sorted(LM_SHAPE):
        raise CellError(f"LM shape {shape!r} must have exactly the keys "
                        f"{list(LM_SHAPE)}")
    if shape["kind"] not in LM_KINDS:
        raise CellError(f"LM shape kind {shape['kind']!r} is not one of "
                        f"{list(LM_KINDS)}")
    for k in ("seq_len", "global_batch"):
        if not isinstance(shape[k], int) or shape[k] < 1:
            raise CellError(f"LM shape {k} {shape[k]!r} is not a positive "
                            f"whole number")
    unlisted = [k for k in net.get("overrides", {})
                if k not in config.get("reduced", ())]
    if unlisted:
        raise CellError(f"overrides {unlisted} of network {net['name']!r} "
                        f"are not listed under the configuration's "
                        f"`reduced`")
    path = root / BENCH_DIR / "reference" / "networks" / f"{net['name']}.py"
    if not path.is_file():
        raise CellError(f"LM network {net['name']!r} has no reference "
                        f"network file {path}")
    return dict(net, reference_file=str(path))


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
    if "lm" in config["network"]:
        config["network"] = _lm_network(config, root)
    traffic = _load_json(root / BENCH_DIR / "traffic"
                         / f"{w['traffic']}.json")
    return Cell(name=workload, chips=int(w["chips"]), config=config,
                traffic=traffic,
                end_to_end=[m for m in spec["end_to_end"]
                            if _applies(m, workload)],
                per_layer=[m for m in spec["per_layer"]
                           if _applies(m, workload)],
                root=root)
