"""Tests for `repro.analysis` (trimlint).

Three layers:

  * fixture tests — tiny synthetic `src/repro` trees, one good and one
    bad variant per rule, so each rule's detection logic is pinned in
    isolation;
  * real-tree tests — HEAD must be clean, and three seeded mutations of
    a *copy* of the live tree (drop a cache-key field, strip a span,
    unseed an RNG) must each produce exactly the expected finding: the
    analyzer is only useful if it actually fires on the bug classes it
    claims to catch, against the real code shape;
  * CLI tests — baseline add/expire round-trip, JSON/SARIF output
    shape, exit codes.
"""
import json
import shutil
import textwrap
from pathlib import Path

import pytest

from repro.analysis import Finding, build_index, run_analysis
from repro.analysis.__main__ import main as trimlint_main
from repro.analysis.rules import RULES, get_rules

REPO = Path(__file__).resolve().parents[1]


def mk_repo(tmp_path: Path, files) -> Path:
    """Materialize a minimal fixture repo ({relpath: source})."""
    root = tmp_path / "fixture"
    for rel, src in files.items():
        p = root / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(src))
    return root


def _copy_repo(tmp_path: Path) -> Path:
    """Copy of the live tree (src + tests) for mutation testing."""
    root = tmp_path / "repo"
    ignore = shutil.ignore_patterns("__pycache__")
    shutil.copytree(REPO / "src", root / "src", ignore=ignore)
    shutil.copytree(REPO / "tests", root / "tests", ignore=ignore)
    return root


def _mutate(root: Path, rel: str, old: str, new: str) -> None:
    p = root / rel
    text = p.read_text()
    assert old in text, f"mutation anchor not found in {rel}: {old!r}"
    p.write_text(text.replace(old, new, 1))


# ---------------------------------------------------------------------------
# R-SYNC fixtures
# ---------------------------------------------------------------------------
_SYNC_DEVICE = """\
    import jax.numpy as jnp
    import numpy as np

    def device_scores(x):
        return jnp.asarray(x) * 2.0
"""

SYNC_BAD = _SYNC_DEVICE + """
    def collect(x):
        s = device_scores(x)
        return np.asarray(s)
"""

SYNC_GOOD_SPAN = _SYNC_DEVICE + """
    def collect(x, tr):
        s = device_scores(x)
        with tr.span("score"):
            return np.asarray(s)
"""

SYNC_GOOD_CALLER = _SYNC_DEVICE + """
    def _pull(x):
        s = device_scores(x)
        return np.asarray(s)

    def collect(x, tr):
        with tr.span("score"):
            return _pull(x)
"""

SYNC_GOOD_HOST = """\
    import numpy as np

    def pack(rows):
        return np.asarray(rows)
"""


def test_sync_unbracketed_force_fires(tmp_path):
    root = mk_repo(tmp_path, {"src/repro/core/score.py": SYNC_BAD})
    findings = run_analysis(root, rules=["R-SYNC"])
    assert [f.rule for f in findings] == ["R-SYNC"]
    assert findings[0].symbol == "collect"
    assert "asarray" in findings[0].message


def test_sync_lexical_span_is_clean(tmp_path):
    root = mk_repo(tmp_path, {"src/repro/core/score.py": SYNC_GOOD_SPAN})
    assert run_analysis(root, rules=["R-SYNC"]) == []


def test_sync_caller_bracket_is_clean(tmp_path):
    root = mk_repo(tmp_path,
                   {"src/repro/core/score.py": SYNC_GOOD_CALLER})
    assert run_analysis(root, rules=["R-SYNC"]) == []


def test_sync_host_only_asarray_is_clean(tmp_path):
    # np.asarray over host data is packing, not a device sync
    root = mk_repo(tmp_path, {"src/repro/core/packer.py": SYNC_GOOD_HOST})
    assert run_analysis(root, rules=["R-SYNC"]) == []


def test_sync_barrier_callers_are_clean(tmp_path):
    # a device-calling helper whose returns are all host-shaped does not
    # taint its callers
    src = _SYNC_DEVICE + """
    def scores_np(x):
        s = device_scores(x)
        with current_tracer().span("score"):
            return np.asarray(s)

    def downstream(x):
        v = scores_np(x)
        return float(v[0])
"""
    root = mk_repo(tmp_path, {"src/repro/core/score.py": src})
    assert run_analysis(root, rules=["R-SYNC"]) == []


# -- @deferred_sync contract ------------------------------------------------
_SYNC_DEFERRED = """\
    import jax.numpy as jnp
    import numpy as np
    from repro.obs import deferred_sync

    @deferred_sync
    def launch(x):
        return jnp.asarray(x) * 2.0
"""


def test_deferred_sync_bare_callsite_fires(tmp_path):
    src = _SYNC_DEFERRED + """
    def run(x):
        return launch(x)
"""
    root = mk_repo(tmp_path, {"src/repro/core/score.py": src})
    findings = run_analysis(root, rules=["R-SYNC"])
    assert len(findings) == 1
    assert "deferred-sync producer" in findings[0].message
    assert findings[0].symbol == "run"


def test_deferred_sync_span_bracketed_is_clean(tmp_path):
    src = _SYNC_DEFERRED + """
    def run(x, tr):
        with tr.span("score"):
            p = launch(x)
        with tr.span("device-wait"):
            return np.asarray(p)
"""
    root = mk_repo(tmp_path, {"src/repro/core/score.py": src})
    assert run_analysis(root, rules=["R-SYNC"]) == []


def test_deferred_sync_caller_bracket_is_clean(tmp_path):
    # the launching span may live one level up (every callsite of the
    # helper that launches is bracketed)
    src = _SYNC_DEFERRED + """
    def _go(x):
        return launch(x)

    def run(x, tr):
        with tr.span("score"):
            return _go(x)
"""
    root = mk_repo(tmp_path, {"src/repro/core/score.py": src})
    assert run_analysis(root, rules=["R-SYNC"]) == []


def test_deferred_sync_unforced_result_still_needs_span(tmp_path):
    # the pin side: a deferred producer can never be laundered into a
    # barrier, so forcing its result outside a span still fires
    src = _SYNC_DEFERRED + """
    def run(x, tr):
        with tr.span("score"):
            p = launch(x)
        return np.asarray(p)
"""
    root = mk_repo(tmp_path, {"src/repro/core/score.py": src})
    findings = run_analysis(root, rules=["R-SYNC"])
    assert len(findings) == 1
    assert "asarray" in findings[0].message
    assert findings[0].symbol == "run"


def test_deferred_sync_stale_marker_fires(tmp_path):
    src = """\
    import numpy as np
    from repro.obs import deferred_sync

    @deferred_sync
    def shuffle(rows):
        return np.asarray(rows)

    def run(rows, tr):
        with tr.span("pack"):
            return shuffle(rows)
"""
    root = mk_repo(tmp_path, {"src/repro/core/packer.py": src})
    findings = run_analysis(root, rules=["R-SYNC"])
    assert len(findings) == 1
    assert "stale marker" in findings[0].message
    assert findings[0].symbol == "shuffle"


def test_live_repo_declares_deferred_producers():
    """The streaming pipeline's launch path is marked and bracketed in
    the live tree (the contract the fixtures above enforce)."""
    from repro.analysis.rules.sync import _Classifier
    idx = build_index(REPO)
    cls = _Classifier(idx)
    assert "repro.search.batch_frontier.fused_launch" in cls.deferred
    assert "repro.search.batch_frontier._dispatch_shards" in cls.deferred
    for d in cls.deferred:
        assert cls.ret_dev[d]           # pinned device-returning


# ---------------------------------------------------------------------------
# R-DET fixtures
# ---------------------------------------------------------------------------
def test_det_unseeded_rng_in_scoring_module(tmp_path):
    bad = """\
    import numpy as np

    def sample(n):
        rng = np.random.default_rng()
        return rng.integers(0, n)
"""
    root = mk_repo(tmp_path, {"src/repro/core/evaluator.py": bad})
    findings = run_analysis(root, rules=["R-DET"])
    assert [f.rule for f in findings] == ["R-DET"]
    assert "unseeded" in findings[0].message
    assert findings[0].symbol == "sample"

    good = bad.replace("default_rng()", "default_rng(n)")
    root2 = mk_repo(tmp_path / "g", {"src/repro/core/evaluator.py": good})
    assert run_analysis(root2, rules=["R-DET"]) == []


def test_det_wallclock_and_global_draw_in_strategy(tmp_path):
    bad = """\
    import random
    import time

    def propose(pool):
        t = time.time()
        return random.choice(pool), t
"""
    root = mk_repo(tmp_path, {"src/repro/search/strategies.py": bad})
    msgs = [f.message for f in run_analysis(root, rules=["R-DET"])]
    assert len(msgs) == 2
    assert any("time.time" in m for m in msgs)
    assert any("random.choice" in m for m in msgs)


def test_det_digest_closure_bans(tmp_path):
    bad = """\
    import hashlib
    import json

    CACHE_FORMAT = 1

    def cache_key(payload):
        for k in set(payload):
            pass
        blob = json.dumps(payload)
        return hashlib.sha256(blob.encode()).hexdigest()
"""
    root = mk_repo(tmp_path, {"src/repro/search/cache.py": bad})
    msgs = [f.message for f in run_analysis(root, rules=["R-DET"])]
    assert len(msgs) == 2
    assert any("sort_keys" in m for m in msgs)
    assert any("set" in m for m in msgs)

    good = bad.replace("set(payload)", "sorted(payload)").replace(
        "json.dumps(payload)", "json.dumps(payload, sort_keys=True)")
    root2 = mk_repo(tmp_path / "g", {"src/repro/search/cache.py": good})
    assert run_analysis(root2, rules=["R-DET"]) == []


def test_det_seeded_rng_outside_digest_closure_is_clean(tmp_path):
    # wall-clock in a non-scoring module (e.g. GC code) is fine
    ok = """\
    import time

    def gc_stale(path):
        return time.time()
"""
    root = mk_repo(tmp_path, {"src/repro/search/cache.py": ok})
    assert run_analysis(root, rules=["R-DET"]) == []


# ---------------------------------------------------------------------------
# R-TRACE fixtures
# ---------------------------------------------------------------------------
_TRACE_MOD = """\
    DRIVER_PHASES = ("score", "pack")
    PHASES = DRIVER_PHASES + ("serve.tick",)
"""


def test_trace_bare_span_and_bad_phase(tmp_path):
    bad = """\
    def run(tr):
        sp = tr.span("leak")
        with tr.span("scoring", phase=True):
            pass
"""
    root = mk_repo(tmp_path, {"src/repro/obs/trace.py": _TRACE_MOD,
                              "src/repro/core/driver.py": bad})
    msgs = [f.message for f in run_analysis(root, rules=["R-TRACE"])]
    assert len(msgs) == 2
    assert any("outside a `with`" in m for m in msgs)
    assert any("not in the canonical" in m for m in msgs)


def test_trace_good_spans_are_clean(tmp_path):
    good = """\
    def run(tr):
        with tr.span("score", phase=True):
            pass
        with tr.span("serve.tick", phase=True):
            pass
        with tr.span("anything-goes-unphased", rows=3):
            pass
"""
    root = mk_repo(tmp_path, {"src/repro/obs/trace.py": _TRACE_MOD,
                              "src/repro/core/driver.py": good})
    assert run_analysis(root, rules=["R-TRACE"]) == []


def test_trace_phase_name_must_be_literal(tmp_path):
    bad = """\
    def run(tr, name):
        with tr.span(name, phase=True):
            pass
"""
    root = mk_repo(tmp_path, {"src/repro/obs/trace.py": _TRACE_MOD,
                              "src/repro/core/driver.py": bad})
    msgs = [f.message for f in run_analysis(root, rules=["R-TRACE"])]
    assert len(msgs) == 1 and "string literal" in msgs[0]


# ---------------------------------------------------------------------------
# R-CACHE fixtures
# ---------------------------------------------------------------------------
_CACHE_FIXTURE = {
    "src/repro/core/workload.py": """\
    import dataclasses

    @dataclasses.dataclass
    class Workload:
        dims: tuple
        sparsity: float
""",
    "src/repro/core/designer.py": """\
    import dataclasses

    @dataclasses.dataclass
    class Level:
        size_words: int

    @dataclasses.dataclass
    class HardwareDesc:
        name: str
        freq: float
""",
    "src/repro/core/mapper.py": """\
    import dataclasses

    @dataclasses.dataclass
    class MapperConfig:
        max_mappings: int
        seed: int
""",
    "src/repro/core/evaluator.py": """\
    def score(wl, hw, cfg):
        return len(wl.dims) * wl.sparsity * hw.freq * cfg.max_mappings
""",
    "src/repro/search/cache.py": """\
    import dataclasses
    import hashlib
    import json

    from ..core.designer import HardwareDesc
    from ..core.mapper import MapperConfig
    from ..core.workload import Workload

    CACHE_FORMAT = 1

    def _workload_sig(wl: Workload):
        return {"dims": list(wl.dims), "sparsity": wl.sparsity}

    def _hw_sig(hw: HardwareDesc):
        return {"freq": hw.freq}

    def _cfg_sig(cfg: MapperConfig):
        return dataclasses.asdict(cfg)

    def cache_key(wl: Workload, hw: HardwareDesc, cfg: MapperConfig,
                  goal):
        payload = {"v": CACHE_FORMAT, "workload": _workload_sig(wl),
                   "hw": _hw_sig(hw), "cfg": _cfg_sig(cfg), "goal": goal}
        blob = json.dumps(payload, sort_keys=True)
        return hashlib.sha256(blob.encode()).hexdigest()
""",
}


def test_cache_complete_key_is_clean(tmp_path):
    root = mk_repo(tmp_path, _CACHE_FIXTURE)
    assert run_analysis(root, rules=["R-CACHE"]) == []


def test_cache_uncovered_field_fires(tmp_path):
    files = dict(_CACHE_FIXTURE)
    files["src/repro/search/cache.py"] = files[
        "src/repro/search/cache.py"].replace(
            ', "sparsity": wl.sparsity', "")
    root = mk_repo(tmp_path, files)
    findings = run_analysis(root, rules=["R-CACHE"])
    assert [f.rule for f in findings] == ["R-CACHE"]
    assert "Workload.sparsity" in findings[0].message
    assert findings[0].path.endswith("core/evaluator.py")


def test_cache_exempt_field_is_quiet(tmp_path):
    # HardwareDesc.name is deliberately excluded (cosmetic identity)
    files = dict(_CACHE_FIXTURE)
    files["src/repro/core/evaluator.py"] = """\
    def score(wl, hw, cfg):
        return (hw.name, wl.sparsity * hw.freq * cfg.max_mappings)
"""
    root = mk_repo(tmp_path, files)
    assert run_analysis(root, rules=["R-CACHE"]) == []


def test_cache_asdict_sweeps_all_fields(tmp_path):
    # cfg.seed is never read explicitly in the sig but asdict covers it
    files = dict(_CACHE_FIXTURE)
    files["src/repro/core/evaluator.py"] = """\
    def score(wl, hw, cfg):
        return wl.sparsity * hw.freq * cfg.seed
"""
    root = mk_repo(tmp_path, files)
    assert run_analysis(root, rules=["R-CACHE"]) == []


# ---------------------------------------------------------------------------
# R-REG fixtures
# ---------------------------------------------------------------------------
_STRATEGIES = """\
    STRATEGIES = {}

    def register(name):
        def deco(cls):
            STRATEGIES[name] = cls
            return cls
        return deco

    @register("alpha")
    class Alpha:
        pass

    @register("beta")
    class Beta:
        pass
"""

_PROGRESS = """\
    EVENT_KINDS = ("arch-started", "arch-finished")

    class ConsoleSink:
        def __call__(self, ev):
            if ev.kind == "arch-started":
                print(ev)
"""

_EMITTER = """\
    def run(stream):
        stream.emit("arch-started")
        stream.emit("arch-finished")
"""


def test_reg_registry_driven_contract_test_covers_all(tmp_path):
    root = mk_repo(tmp_path, {
        "src/repro/search/strategies.py": _STRATEGIES,
        "tests/test_strategy_contract.py": """\
    from repro.search.strategies import STRATEGIES

    def test_contract():
        for name in sorted(STRATEGIES):
            assert name
""",
    })
    assert run_analysis(root, rules=["R-REG"]) == []


def test_reg_literal_coverage_gap_fires(tmp_path):
    root = mk_repo(tmp_path, {
        "src/repro/search/strategies.py": _STRATEGIES,
        "tests/test_strategy_contract.py": """\
    def test_contract():
        assert "alpha"
""",
    })
    findings = run_analysis(root, rules=["R-REG"])
    assert [f.symbol for f in findings] == ["beta"]


def test_reg_missing_contract_test_fires(tmp_path):
    root = mk_repo(tmp_path,
                   {"src/repro/search/strategies.py": _STRATEGIES})
    msgs = [f.message for f in run_analysis(root, rules=["R-REG"])]
    assert len(msgs) == 1 and "missing" in msgs[0]


def test_reg_event_kinds_round_trip(tmp_path):
    # unhandled sink kind + undeclared emit + dead declared kind
    root = mk_repo(tmp_path, {
        "src/repro/obs/progress.py": _PROGRESS.replace(
            '"arch-finished")', '"arch-finished", "dead-kind")'),
        "src/repro/search/driver.py": _EMITTER.replace(
            'emit("arch-finished")', 'emit("arch-typo")'),
    })
    msgs = [f.message for f in run_analysis(root, rules=["R-REG"])]
    assert any("arch-typo" in m and "not a declared" in m for m in msgs)
    assert any("dead-kind" in m and "nothing" in m for m in msgs)
    assert any("no branch" in m for m in msgs)


def test_reg_generic_sink_fallback_is_enough(tmp_path):
    progress = _PROGRESS + """\

    class VerboseSink:
        pass
"""
    progress = progress.replace(
        "                print(ev)",
        "                print(ev)\n            else:\n"
        "                print(ev.kind)")
    root = mk_repo(tmp_path, {
        "src/repro/obs/progress.py": progress,
        "src/repro/search/driver.py": _EMITTER,
    })
    assert run_analysis(root, rules=["R-REG"]) == []


# ---------------------------------------------------------------------------
# the real tree
# ---------------------------------------------------------------------------
def test_head_is_clean():
    """Tier-1 pin: the live repo passes its own analyzer with an empty
    baseline (all true positives are fixed, not grandfathered)."""
    assert run_analysis(REPO) == []


def test_mutation_dropped_cache_field_fires_r_cache(tmp_path):
    root = _copy_repo(tmp_path)
    _mutate(root, "src/repro/search/cache.py",
            '            "in_zf": round(wl.input_zero_frac, 9),\n', "")
    findings = run_analysis(root, rules=["R-CACHE"])
    assert findings and all(f.rule == "R-CACHE" for f in findings)
    assert any("Workload.input_zero_frac" in f.message for f in findings)
    # dropping a field changes the key shape -> format-bump finding too
    assert any("CACHE_FORMAT" in f.message for f in findings)


def test_mutation_payload_key_without_bump_fires_r_cache(tmp_path):
    root = _copy_repo(tmp_path)
    _mutate(root, "src/repro/search/cache.py",
            '"scorer": scorer,', '"scorer": scorer, "extra": 1,')
    findings = run_analysis(root, rules=["R-CACHE"])
    assert len(findings) == 1
    assert "CACHE_FORMAT" in findings[0].message
    assert "bump" in findings[0].message


def test_mutation_span_stripped_fires_r_sync(tmp_path):
    root = _copy_repo(tmp_path)
    _mutate(root, "src/repro/search/batch_frontier.py",
            "            with tr.span(\"fused.jnp-group\", jobs=len(chunk),\n"
            "                         rows=rows) as sp:\n"
            "                if tr.enabled:\n"
            "                    sp.set(eligible_rows=_eligible_rows(chunk, "
            "arrays,\n"
            "                                                        engine))\n"
            "                _eval_group(sig, chunk, jobs, arrays, key, "
            "out)",
            "            _eval_group(sig, chunk, jobs, arrays, key, out)")
    findings = run_analysis(root, rules=["R-SYNC"])
    assert findings and all(f.rule == "R-SYNC" for f in findings)
    assert {f.symbol for f in findings} == {"_eval_group"}
    assert all(f.path.endswith("batch_frontier.py") for f in findings)


def test_mutation_unseeded_rng_fires_r_det(tmp_path):
    root = _copy_repo(tmp_path)
    _mutate(root, "src/repro/core/mapper.py",
            "np.random.default_rng(seed)", "np.random.default_rng()")
    findings = run_analysis(root, rules=["R-DET"])
    assert len(findings) == 1
    assert findings[0].rule == "R-DET"
    assert findings[0].symbol == "sample_index_rows"
    assert "unseeded" in findings[0].message


# Coverage proofs for the DSE service module: the service's digest,
# spans, and event kinds are held to the same static discipline as the
# search core — each mutation must fire the corresponding rule.
def test_mutation_unsorted_service_digest_fires_r_det(tmp_path):
    # SearchQuery.digest is a DIGEST_ROOTS closure root: dropping
    # sort_keys lets dict order leak into the coalescing identity
    root = _copy_repo(tmp_path)
    _mutate(root, "src/repro/serve/dse_service.py",
            "json.dumps(self.signature(), sort_keys=True,\n"
            "                              default=str)",
            "json.dumps(self.signature(), default=str)")
    findings = run_analysis(root, rules=["R-DET"])
    assert len(findings) == 1
    assert findings[0].path.endswith("serve/dse_service.py")
    assert findings[0].symbol == "SearchQuery.digest"
    assert "sort_keys" in findings[0].message


def test_mutation_bogus_service_phase_fires_r_trace(tmp_path):
    root = _copy_repo(tmp_path)
    _mutate(root, "src/repro/serve/dse_service.py",
            'self.tracer.span("service.job", digest=',
            'self.tracer.span("service.job", phase=True, digest=')
    findings = run_analysis(root, rules=["R-TRACE"])
    assert len(findings) == 1
    assert findings[0].path.endswith("serve/dse_service.py")
    assert "not in the canonical" in findings[0].message


def test_mutation_typoed_service_event_kind_fires_r_reg(tmp_path):
    root = _copy_repo(tmp_path)
    _mutate(root, "src/repro/serve/dse_service.py",
            'job.emit("job-admitted"', 'job.emit("job-started"')
    findings = run_analysis(root, rules=["R-REG"])
    msgs = [f.message for f in findings]
    # the typo'd emit is flagged where it happens...
    assert any("'job-started'" in m and "not a declared" in m
               for m in msgs)
    # ...and the now-orphaned declared kind is flagged as dead
    assert any("'job-admitted'" in m and "nothing" in m for m in msgs)


# ---------------------------------------------------------------------------
# engine / finding plumbing
# ---------------------------------------------------------------------------
def test_fingerprint_is_line_independent():
    a = Finding(rule="R-X", path="src/repro/a.py", line=10, col=0,
                message="m", symbol="f")
    b = Finding(rule="R-X", path="src/repro/a.py", line=99, col=4,
                message="m", symbol="f")
    c = Finding(rule="R-X", path="src/repro/a.py", line=10, col=0,
                message="other", symbol="f")
    assert a.fingerprint() == b.fingerprint() != c.fingerprint()


def test_get_rules_rejects_unknown_ids():
    assert {r.id for r in get_rules()} == \
        {"R-CACHE", "R-SYNC", "R-DET", "R-TRACE", "R-REG"}
    with pytest.raises(KeyError):
        get_rules(["R-NOPE"])


def test_rules_have_unique_ids_and_descriptions():
    ids = [r.id for r in RULES]
    assert len(ids) == len(set(ids))
    assert all(r.description for r in RULES)


# ---------------------------------------------------------------------------
# CLI: baseline round-trip, output formats, exit codes
# ---------------------------------------------------------------------------
def test_cli_baseline_roundtrip(tmp_path, capsys):
    root = mk_repo(tmp_path, {"src/repro/core/score.py": SYNC_BAD})
    bl = tmp_path / "bl.json"
    argv = ["--root", str(root), "--rules", "R-SYNC",
            "--baseline", str(bl)]

    assert trimlint_main(argv) == 1                   # fresh finding
    assert trimlint_main(argv + ["--write-baseline"]) == 0
    data = json.loads(bl.read_text())
    assert data["version"] == 1 and len(data["findings"]) == 1
    assert data["findings"][0]["rule"] == "R-SYNC"

    assert trimlint_main(argv) == 0                   # suppressed
    assert trimlint_main(argv + ["--strict"]) == 0

    # fix the finding -> the baseline entry goes stale and strict fails
    (root / "src/repro/core/score.py").write_text(
        textwrap.dedent(SYNC_GOOD_SPAN))
    assert trimlint_main(argv) == 0
    assert trimlint_main(argv + ["--strict"]) == 1
    out = capsys.readouterr().out
    assert "stale" in out


def test_cli_json_output(tmp_path, capsys):
    root = mk_repo(tmp_path, {"src/repro/core/score.py": SYNC_BAD})
    rc = trimlint_main(["--root", str(root), "--rules", "R-SYNC",
                        "--format", "json"])
    assert rc == 1
    report = json.loads(capsys.readouterr().out)
    assert report["version"] == 1
    assert len(report["findings"]) == 1
    f = report["findings"][0]
    assert f["rule"] == "R-SYNC" and f["path"].endswith("score.py")
    assert f["fingerprint"]


def test_cli_sarif_output(tmp_path, capsys):
    root = mk_repo(tmp_path, {"src/repro/core/score.py": SYNC_BAD})
    out = tmp_path / "out.sarif"
    rc = trimlint_main(["--root", str(root), "--rules", "R-SYNC",
                        "--format", "sarif", "--output", str(out)])
    assert rc == 1
    sarif = json.loads(out.read_text())
    assert sarif["version"] == "2.1.0"
    run = sarif["runs"][0]
    assert run["tool"]["driver"]["name"] == "trimlint"
    assert any(r["id"] == "R-SYNC"
               for r in run["tool"]["driver"]["rules"])
    res = run["results"]
    assert len(res) == 1 and res[0]["ruleId"] == "R-SYNC"
    assert res[0]["partialFingerprints"]["trimlint/v1"]
    loc = res[0]["locations"][0]["physicalLocation"]
    assert loc["artifactLocation"]["uri"].endswith("score.py")


def test_cli_exit_codes(tmp_path, capsys):
    root = mk_repo(tmp_path, {"src/repro/core/score.py": SYNC_GOOD_SPAN})
    assert trimlint_main(["--root", str(root)]) == 0
    assert "clean" in capsys.readouterr().out
    assert trimlint_main(["--root", str(root),
                          "--rules", "R-BOGUS"]) == 2
    assert trimlint_main(["--list-rules"]) == 0
    listed = capsys.readouterr().out
    for rid in ("R-CACHE", "R-SYNC", "R-DET", "R-TRACE", "R-REG"):
        assert rid in listed
