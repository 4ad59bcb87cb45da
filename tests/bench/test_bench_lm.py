"""A configuration may name an LM block lowered by the program's
`lower_block`: the harness takes it as new files alone (a configuration
and its reference network file), judges it against the reference's own
workloads, and refuses a malformed one before set-up.  The CNN
configurations build the same task as before."""
import ast
import json

import pytest

from bench_tiny import (REPO, SMOLLM_REFERENCE, TINY_LM_CONFIG, make_root,
                        run_tiny)
from test_bench_faults import FAULTS

NUMBERS = ("missing", "invalid", "query_mismatch", "estimate_gap", "regret")


def _lm_root(tmp_path, config=TINY_LM_CONFIG, reference=SMOLLM_REFERENCE):
    return make_root(tmp_path, lm_config=config, lm_reference=reference)


def test_lm_cell_is_correct(tmp_path, monkeypatch, capsys):
    from bench.reference import check
    # a pool of two reference workers, as a cell with many mapspaces
    # has: each loads the network file by the path the cell resolved
    monkeypatch.setattr(check, "MAX_WORKERS", 2)
    monkeypatch.setattr(check, "SPACES_PER_WORKER", 1)
    root = _lm_root(tmp_path)
    rc, result, err = run_tiny(root, monkeypatch, capsys,
                               workload="tiny-lm.bypass", seed=3000000041)
    assert rc == 0, err
    assert result["correct"] is True
    assert {k: v["value"] for k, v in result["compared"].items()} \
        == dict.fromkeys(NUMBERS, 0)


def test_lm_reference_with_a_width_off_by_one_is_not_correct(
        tmp_path, monkeypatch, capsys):
    wrong = SMOLLM_REFERENCE.replace("FFN = 1536", "FFN = 1537")
    assert wrong != SMOLLM_REFERENCE
    rc, result, err = run_tiny(_lm_root(tmp_path, reference=wrong),
                               monkeypatch, capsys,
                               workload="tiny-lm.bypass", seed=3000000041)
    assert rc == 0, err
    assert result["correct"] is False
    assert result["compared"]["invalid"]["value"] > 0
    assert "FAILED" in err


@pytest.mark.parametrize("fault,target,wrap,number", FAULTS)
def test_lm_fault_is_not_correct(tmp_path, monkeypatch, capsys, fault,
                                 target, wrap, number):
    import importlib
    mod_name, attr = target.rsplit(".", 1)
    mod = importlib.import_module(mod_name)
    monkeypatch.setattr(mod, attr, wrap(getattr(mod, attr)))
    rc, result, err = run_tiny(_lm_root(tmp_path), monkeypatch, capsys,
                               workload="tiny-lm.bypass", seed=11)
    assert rc == 0, err
    assert result["correct"] is False, fault
    c = result["compared"][number]
    assert c["value"] > c["limit"], (fault, c)


def _network(**changes):
    net = dict(TINY_LM_CONFIG["network"], **changes)
    return {k: v for k, v in net.items() if v is not None}


@pytest.mark.parametrize("what,config,reference", [
    ("no reference network file", TINY_LM_CONFIG, None),
    ("no shape", dict(TINY_LM_CONFIG, network=_network(shape=None)),
     SMOLLM_REFERENCE),
    ("kind not train, prefill or decode",
     dict(TINY_LM_CONFIG, network=_network(
         shape={"seq_len": 32, "global_batch": 2, "kind": "serve"})),
     SMOLLM_REFERENCE),
    ("override not listed under reduced",
     dict(TINY_LM_CONFIG, reduced={}), SMOLLM_REFERENCE),
])
def test_malformed_lm_config_exits_2_before_setup(
        tmp_path, monkeypatch, capsys, what, config, reference):
    from bench import run

    def no_setup(*a, **k):
        raise AssertionError("set-up reached")
    monkeypatch.setattr(run, "make_query", no_setup)
    rc, result, err = run_tiny(_lm_root(tmp_path, config, reference),
                               monkeypatch, capsys, workload="tiny-lm.bypass")
    assert rc == 2 and result is None, what
    assert "bench:" in err and "set-up reached" not in err


@pytest.mark.parametrize("rank,report,number", [
    ("bfloat16", "float64", "regret"),
    ("float32", "float32", "estimate_gap")])
def test_lm_control_is_not_correct(tmp_path, capsys, rank, report, number):
    from bench import control
    rc = control.main(["--workload", "tiny-lm.nobypass", "--seeds", "2",
                       "--rank", rank, "--report", report],
                      root=_lm_root(tmp_path))
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and out["correct"] is False
    c = out["compared"][number]
    assert c["value"] > c["limit"]


@pytest.mark.parametrize("config", ["alexnet-cifar-fig20",
                                    "resnet18-im-fig20"])
def test_program_task_of_a_cnn_is_the_analysed_network(config):
    from repro.core import analyze
    from repro.core.task_analyst import NETWORKS
    from bench.query import program_task
    cfg = json.loads((REPO / "bench" / "configs" / f"{config}.json")
                     .read_text())
    net = cfg["network"]
    want = analyze(NETWORKS[net["name"]](batch_size=net["batch_size"],
                                         processing=net["processing"]))
    got = program_task(cfg)
    assert type(got) is type(want)
    assert len(got.intra) > 0
    for field in ("intra", "preproc", "activations"):
        assert getattr(got, field) == getattr(want, field), field


def repro_imports(source: str):
    """The imports of `repro` in a module's source: `import`, `from ...
    import` and `importlib.import_module` / `__import__` of a constant."""
    found = []
    for node in ast.walk(ast.parse(source)):
        names = []
        if isinstance(node, ast.Import):
            names = [a.name for a in node.names]
        elif isinstance(node, ast.ImportFrom) and node.level == 0:
            names = [node.module or ""]
        elif isinstance(node, ast.Call) and node.args \
                and isinstance(node.args[0], ast.Constant) \
                and isinstance(node.args[0].value, str):
            f = node.func
            fname = f.attr if isinstance(f, ast.Attribute) else \
                getattr(f, "id", "")
            if fname in ("import_module", "__import__"):
                names = [node.args[0].value]
        found += [n for n in names if n.split(".")[0] == "repro"]
    return found


@pytest.mark.parametrize("planted", [
    "import repro", "import repro.configs as c",
    "from repro.core.lower_lm import lower_block",
    "import importlib\nimportlib.import_module('repro.configs')",
    "__import__('repro')"])
def test_import_scan_finds_repro(planted):
    assert repro_imports(SMOLLM_REFERENCE + "\n" + planted + "\n")


def test_reference_networks_import_nothing_of_the_program():
    files = sorted((REPO / "bench" / "reference" / "networks").glob("*.py"))
    sources = {str(p): p.read_text() for p in files}
    sources["tests: SMOLLM_REFERENCE"] = SMOLLM_REFERENCE
    assert {k: repro_imports(s) for k, s in sources.items()} \
        == {k: [] for k in sources}
