"""The DeepSeek-V2-Lite configuration: the program's lowering of it
(`bench.query.program_task`) is its reference network file's, workload
by workload; a tiny cell of it reads correct, and one whose reference
has a width off by one does not."""
import copy
import json

import pytest

from bench_tiny import REPO, TINY_CONFIG, make_root, run_tiny
from test_bench_lm import repro_imports

CONFIG_FILE = REPO / "bench" / "configs" / "deepseek-v2-lite-train4k.json"
REFERENCE_FILE = (REPO / "bench" / "reference" / "networks"
                  / "deepseek-v2-lite.py")

# one dense and one MoE layer with 8 of the 64 experts, at the published
# widths, training at sequence 32 and batch 2
TINY_DEEPSEEK = dict(
    TINY_CONFIG, name="tiny-deepseek",
    network={"name": "deepseek-v2-lite", "lm": "deepseek-v2-lite-16b",
             "shape": {"seq_len": 32, "global_batch": 2, "kind": "train"},
             "overrides": {"n_layers": 2, "experts_held": 8}},
    reduced={"n_layers": "the dense layer and one MoE layer",
             "experts_held": "8 of the 64 routed experts"})


def _published():
    return json.loads(CONFIG_FILE.read_text())


def _small():
    cfg = copy.deepcopy(_published())
    cfg["network"]["shape"] = {"seq_len": 64, "global_batch": 2,
                               "kind": "train"}
    cfg["network"]["overrides"] = {"n_layers": 3, "experts_held": 8}
    return cfg


def _rows(task):
    return [(w.name, w.layer, w.phase, w.dims) for w in task.intra]


@pytest.mark.parametrize("make", [_published, _small],
                         ids=["published", "small"])
def test_program_task_is_the_reference_network(make):
    from bench.query import program_task
    from bench.reference.check import reference_task
    config = dict(make())
    config["network"] = dict(config["network"],
                             reference_file=str(REFERENCE_FILE))
    got, want = _rows(program_task(config)), _rows(reference_task(config))
    assert len(got) == len(want)
    for j, (g, w) in enumerate(zip(got, want)):
        assert g == w, j


def test_published_file_is_one_chip_of_eight():
    cfg = _published()
    net = cfg["network"]
    assert net["shape"] == {"seq_len": 4096, "global_batch": 32,
                            "kind": "train"}
    assert net["overrides"] == {"experts_held": 8}
    assert sorted(cfg["reduced"]) == ["experts_held", "global_batch",
                                      "lattice", "max_mappings"]
    # the published config.json's keys, unchanged
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"],
            cfg["vocab_size"]) == (27, 64, 102400)


@pytest.fixture
def deepseek_root(tmp_path):
    return make_root(tmp_path, lm_config=TINY_DEEPSEEK,
                     lm_reference=REFERENCE_FILE.read_text())


def test_tiny_deepseek_cell_is_correct(deepseek_root, monkeypatch, capsys):
    rc, result, err = run_tiny(deepseek_root, monkeypatch, capsys,
                               workload="tiny-lm.bypass", seed=3000016021)
    assert rc == 0, err
    assert result["correct"] is True
    assert {k: v["value"] for k, v in result["compared"].items()} \
        == dict.fromkeys(("missing", "invalid", "query_mismatch",
                          "estimate_gap", "regret"), 0)


def test_reference_with_kv_rank_off_by_one_is_not_correct(
        tmp_path, monkeypatch, capsys):
    source = REFERENCE_FILE.read_text()
    wrong = source.replace("KV_LORA_RANK = 512", "KV_LORA_RANK = 513")
    assert wrong != source
    root = make_root(tmp_path, lm_config=TINY_DEEPSEEK, lm_reference=wrong)
    rc, result, err = run_tiny(root, monkeypatch, capsys,
                               workload="tiny-lm.bypass", seed=3000016021)
    assert rc == 0, err
    assert result["correct"] is False
    assert result["compared"]["invalid"]["value"] > 0
    assert "FAILED" in err


def test_import_scan_covers_the_reference_network():
    found = sorted(p.name for p in REFERENCE_FILE.parent.glob("*.py"))
    assert REFERENCE_FILE.name in found
    assert repro_imports(REFERENCE_FILE.read_text()) == []
