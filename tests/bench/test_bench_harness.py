"""The harness finds a configuration, a traffic and a per-layer metric
by name from files alone, and refuses to run without a TPU."""
import json
import os
import shutil
import subprocess
import sys

from bench_tiny import REPO, make_root, run_tiny

RUN = [sys.executable, "bench/run.py", "--workload",
       "alexnet-cifar-fig20.nobypass", "--seed", "3000000017",
       "--seconds", "1", "--trace", "0"]
CPU_ENV = {**os.environ, "JAX_PLATFORMS": "cpu"}

ROWS_READER = '''"""Test metric: thousands of rows per query on the jnp path."""


def read(ctx):
    return ctx.jnp_rows / 1000.0 if ctx.jnp_rows else None
'''


def test_new_config_traffic_and_metric_are_files(tmp_path, monkeypatch,
                                                 capsys):
    traffic = json.loads((REPO / "bench" / "traffic" / "bypass.json")
                         .read_text())
    traffic["search"]["round_size"] = 1
    root = make_root(tmp_path,
                     extra_metrics=[("jnp_krows", "krows", ROWS_READER)],
                     extra_traffic=("oneround", traffic))
    rc, result, err = run_tiny(root, monkeypatch, capsys,
                               workload="tiny.oneround", trace=1)
    assert rc == 0, err
    assert result["correct"] is True
    assert result["metrics"]["jnp_krows"]["unit"] == "krows"
    assert result["metrics"]["jnp_krows"]["value"] > 0
    assert {"build_s", "score_s"} <= set(result["metrics"])
    # nothing ran on a device here: the device readers read nothing
    assert "device_idle_pct" not in result["metrics"]
    assert list(result)[-1] == "compared"
    assert err.strip().splitlines()[-1].startswith("regret=")


def test_untraced_run_reports_end_to_end_metrics(tmp_path, monkeypatch,
                                                 capsys):
    rc, result, err = run_tiny(make_root(tmp_path), monkeypatch, capsys,
                               workload="tiny.bypass", seed=3000000029)
    assert rc == 0, err
    assert set(result["metrics"]) == {"query_s", "setup_s"}
    assert result["correct"] is True and result["attempted"] >= 1
    assert all(v["value"] <= v["limit"]
               for v in result["compared"].values())
    assert set(result["device"]) >= {"platform", "kind", "count",
                                     "memory_peak_bytes"}


def test_unknown_cell_exits_nonzero(tmp_path, monkeypatch, capsys):
    rc, result, err = run_tiny(make_root(tmp_path), monkeypatch, capsys,
                               workload="tiny.nosuch")
    assert rc != 0 and result is None and "tiny.nosuch" in err


def test_without_tpu_exits_nonzero_naming_the_platform():
    p = subprocess.run(RUN, cwd=REPO, env=CPU_ENV, capture_output=True,
                       text=True, timeout=300)
    assert p.returncode != 0
    assert "no TPU" in p.stderr and "'cpu'" in p.stderr
    assert "{" not in p.stdout


def test_benchmark_files_alone_do_not_run(tmp_path):
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    shutil.copytree(REPO / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = subprocess.run(RUN, cwd=tmp_path, env=CPU_ENV, capture_output=True,
                       text=True, timeout=300)
    assert p.returncode != 0
    assert "{" not in p.stdout
