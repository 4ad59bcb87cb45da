"""A tiny cell for the benchmark's CPU tests: a root directory holding a
BENCHMARK.json, a configuration, the traffic files and the metric
readers, as a checkout would, at a size the CPU scores in a second."""
from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))
if str(REPO / "src") not in sys.path:
    sys.path.insert(0, str(REPO / "src"))

TINY_CONFIG = {
    "name": "tiny",
    "source": "test",
    "network": {"name": "alexnet-cifar", "batch_size": 4,
                "processing": "Inference"},
    "lattice": {"num_pes": [64], "rf_words": [256],
                "gbuf_words": [65536, 131072], "bits": 32,
                "zero_skip": True},
    "goal": "edp",
    "max_mappings": 96,
}


def make_root(tmp: Path, extra_metrics=(), extra_traffic=None) -> Path:
    """tmp/BENCHMARK.json + tmp/bench/{configs,traffic,metrics}: the
    repository's traffic and metric files, a tiny configuration, and
    cells `tiny.nobypass` and `tiny.bypass` that report every metric."""
    bench = tmp / "bench"
    for d in ("traffic", "metrics"):
        shutil.copytree(REPO / "bench" / d, bench / d, dirs_exist_ok=True)
    (bench / "configs").mkdir(parents=True, exist_ok=True)
    (bench / "configs" / "tiny.json").write_text(json.dumps(TINY_CONFIG))
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    spec["configs"] = [{"name": "tiny", "source": "test",
                        "file": "bench/configs/tiny.json", "reduced": [],
                        "why": "test"}]
    traffics = ["nobypass", "bypass"]
    if extra_traffic is not None:
        name, body = extra_traffic
        (bench / "traffic" / f"{name}.json").write_text(json.dumps(body))
        traffics.append(name)
    spec["workloads"] = [{"name": f"tiny.{t}", "config": "tiny",
                          "traffic": t, "chips": 1, "why": "test"}
                         for t in traffics]
    for m in spec["per_layer"]:
        m.pop("workloads", None)
    for name, unit, source in extra_metrics:
        (bench / "metrics" / f"{name}.py").write_text(source)
        spec["per_layer"].append({"name": name, "unit": unit,
                                  "better": "lower", "source": "program_span",
                                  "layer": "test", "moves": "query_s"})
    (tmp / "BENCHMARK.json").write_text(json.dumps(spec))
    return tmp


def run_tiny(root: Path, monkeypatch, capsys, workload="tiny.nobypass",
             seed=7, trace=0):
    """Drive `bench/run.py` on the CPU (the chip check skipped) ->
    (exit code, result dict or None, stderr)."""
    from bench import run
    monkeypatch.setattr(run, "enable_cache", lambda root: "off")
    rc = run.main(["--workload", workload, "--seed", str(seed),
                   "--seconds", "0.01", "--trace", str(trace)],
                  root=root, require_tpu=False)
    cap = capsys.readouterr()
    lines = cap.out.strip().splitlines()
    result = json.loads(lines[-1]) if rc == 0 and lines else None
    return rc, result, cap.err
