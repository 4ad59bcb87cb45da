"""A tiny cell for the benchmark's CPU tests: a root directory holding a
BENCHMARK.json, a configuration, the traffic files and the metric
readers, as a checkout would, at a size the CPU scores in a second; and,
where asked, a tiny LM configuration with its reference network file."""
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

# SmolLM-135M, one layer, training at sequence 32 and batch 2: the program
# lowers it with `lower_block`, the reference from SMOLLM_REFERENCE.
TINY_LM_CONFIG = dict(
    TINY_CONFIG, name="tiny-lm",
    network={"name": "smollm-135m-tiny", "lm": "smollm-135m",
             "shape": {"seq_len": 32, "global_batch": 2, "kind": "train"},
             "overrides": {"n_layers": 1}},
    reduced={"n_layers": "one of the 30 layers"})

# The reference network file of TINY_LM_CONFIG, from SmolLM-135M's
# published config.json (huggingface.co/HuggingFaceTB/SmolLM-135M).
SMOLLM_REFERENCE = '''"""SmolLM-135M lowered to matrix multiplications:
one block (GQA attention, causal, and a SwiGLU MLP) per layer and the LM
head; in training each also as its input-gradient (BW) and
weight-gradient (WG) product."""
from bench.reference.trim.task_analyst import TaskWorkloads
from bench.reference.trim.workload import matmul_workload

HIDDEN = 576
HEADS = 9
KV_HEADS = 3
HEAD_DIM = 64
FFN = 1536
VOCAB = 49152
LAYERS = 30


def _phases(mms, training):
    fw = [matmul_workload(rows=r, cols=c, inner=k, name=n)
          for n, r, c, k in mms]
    if not training:
        return fw
    grads = []
    for n, r, c, k in mms:
        grads.append(matmul_workload(rows=r, cols=k, inner=c,
                                     name=n + ".BW", phase="BW"))
        grads.append(matmul_workload(rows=k, cols=c, inner=r,
                                     name=n + ".WG", phase="WG"))
    return fw + grads


def task(config):
    net = config["network"]
    shape = net["shape"]
    b, s, kind = shape["global_batch"], shape["seq_len"], shape["kind"]
    layers = net.get("overrides", {}).get("n_layers", LAYERS)
    sq = 1 if kind == "decode" else s
    t = b * sq
    att = b * HEADS * sq if kind == "decode" else b * HEADS * sq // 2
    block = [("q", t, HEADS * HEAD_DIM, HIDDEN),
             ("k", t, KV_HEADS * HEAD_DIM, HIDDEN),
             ("v", t, KV_HEADS * HEAD_DIM, HIDDEN),
             ("scores", att, s, HEAD_DIM),
             ("attn_v", att, HEAD_DIM, s),
             ("o", t, HIDDEN, HEADS * HEAD_DIM),
             ("mlp_up", t, 2 * FFN, HIDDEN),
             ("mlp_down", t, HIDDEN, FFN)]
    training = kind == "train"
    intra = (_phases(block, training) * layers
             + _phases([("lm_head", t, VOCAB, HIDDEN)], training))
    return TaskWorkloads(intra=intra, preproc=[], activations=[])
'''


def make_root(tmp: Path, extra_metrics=(), extra_traffic=None,
              lm_config=None, lm_reference=None) -> Path:
    """tmp/BENCHMARK.json + tmp/bench/{configs,traffic,metrics}: the
    repository's traffic and metric files, a tiny configuration, and
    cells `tiny.nobypass` and `tiny.bypass` that report every metric.
    With `lm_config` also that configuration as `tiny-lm`, with cells
    `tiny-lm.nobypass` and `tiny-lm.bypass`, and with `lm_reference` its
    reference network file: new files only, as a later configuration
    brings them."""
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
    if lm_config is not None:
        (bench / "configs" / "tiny-lm.json").write_text(
            json.dumps(lm_config))
        spec["configs"].append({"name": "tiny-lm", "source": "test",
                                "file": "bench/configs/tiny-lm.json",
                                "reduced": [], "why": "test"})
        spec["workloads"] += [{"name": f"tiny-lm.{t}", "config": "tiny-lm",
                               "traffic": t, "chips": 1, "why": "test"}
                              for t in ("nobypass", "bypass")]
    if lm_reference is not None:
        networks = bench / "reference" / "networks"
        networks.mkdir(parents=True, exist_ok=True)
        (networks / f"{lm_config['network']['name']}.py").write_text(
            lm_reference)
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
