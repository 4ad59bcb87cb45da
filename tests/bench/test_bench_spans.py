"""The program's spans as the benchmark reads them: on the profiler's
clock in the host plane, per thread role, and through the readers of
the span metrics (`bench/spans.py`)."""
import json
import tempfile
import types

import pytest

from bench_tiny import REPO, TINY_CONFIG, make_root
from bench import run as bench_run
from bench import spans as bench_spans
from bench import trace_reduce
from bench.query import make_query


def _traffic(name, **search):
    t = json.loads((REPO / "bench" / "traffic" / f"{name}.json").read_text())
    t["search"].update(search)
    return t


def test_phase_spans_sit_in_the_host_plane_on_the_tracer_clock():
    # one design per round: the prefetch thread builds the second
    query = make_query(TINY_CONFIG, _traffic("nobypass", round_size=1), 11)
    query(trace=True)
    log_dir = tempfile.mkdtemp(prefix="spans-test-")
    rep, planes, (t0_ns, _), t_enter = bench_run._profiled(query, log_dir)
    spans = rep.tracer.buffer.snapshot()
    hs = bench_spans.host_spans(planes, {s.name for s in spans})
    phases = [s for s in spans if s.phase]
    assert phases and len(hs) == len(spans)
    # each thread is a line of its own, on which its roles' spans sit
    lines = {line for name, _, _, line in hs if name == "pack"}
    assert len(lines) == 2
    assert {s.role for s in phases} == {"main", "prefetch"}
    plane = {}
    for name, a, b, _ in hs:
        plane.setdefault(name, []).append((a, b))
    mapped = {}
    for name, a, b in bench_run._host_phases(rep, t0_ns, t_enter):
        mapped.setdefault(name, []).append((a, b))
    for name in {s.name for s in phases}:
        mine = sorted((s for s in phases if s.name == name),
                      key=lambda s: s.t0)
        theirs = sorted(plane[name])
        assert len(mine) == len(theirs)
        for s, (a, b), (ma, mb) in zip(mine, theirs, sorted(mapped[name])):
            # the same durations, within 1 ms
            assert abs(s.duration - (b - a) * 1e-9) < 1e-3
            # and where the perf_counter mapping puts them, within 1 ms
            assert abs(ma - a) < 1e6 and abs(mb - b) < 1e6
    assert bench_spans.plane_agreement(spans, hs, t0_ns, t_enter) < 1e-3


def test_span_readers_read_values_in_a_tiny_run(tmp_path, monkeypatch,
                                                capsys):
    root = make_root(tmp_path, extra_traffic=(
        "kernel", _traffic("nobypass", backend="pallas")))
    monkeypatch.setattr(bench_spans, "enable_cache", lambda root: "off")
    rc = bench_spans.main(["--workload", "tiny.kernel", "--seed",
                           "3000000013", "--queries", "1"],
                          root=root, require_tpu=False)
    out = capsys.readouterr().out.strip().splitlines()
    assert rc == 0
    assert out[0].startswith("tracing cost: ")
    assert out[1].startswith("program per query: ")
    assert out[2].startswith("idle by span: ")
    result = json.loads(out[-1])
    m = result["metrics"]
    assert set(m) == set(bench_spans.SPAN_METRICS)
    assert m["kernel_pack_s"] > 0 and m["kernel_transfer_s"] > 0
    assert m["kernel_launch_s"] > 0 and m["build_wait_s"] > 0
    assert m["jit_s"] >= 0
    c = result["checks"]
    # every span the program opens is in the host plane (JAX's own events
    # are recorded after the fact), on the tracer's clock
    assert c["host_spans"] == c["spans"] > 0
    assert c["plane_agreement_s"] < 1e-3
    assert c["kernel_spans_over_group"] <= 1.0 + 1e-9
    assert c["kernel_spans_over_group"] > 0.5
    assert c["main_phase_over_wall"] <= 1.0
    program = json.loads(out[1].split(": ", 1)[1])
    assert "kernel.launch" in program["self_s"]
    assert program["queries"][0]["wall_s"] > 0


def test_span_readers_read_nothing_from_the_harness_context():
    """The harness's `Context` holds no span seconds yet: each reader
    returns nothing and does not raise."""
    ctx = bench_run.Context({"pack": 1.0}, 0, 10, None, (4, 3), None)
    for name in bench_spans.SPAN_METRICS:
        mod = types.ModuleType(name)
        src = (REPO / "bench" / "metrics" / f"{name}.py").read_text()
        exec(compile(src, name, "exec"), mod.__dict__)
        assert mod.read(ctx) is None


def test_span_seconds_total_and_self():
    from repro.obs import Tracer
    tr = Tracer()
    w0 = tr.buffer.t_wall0
    with tr.span("score", phase=True):
        tr.record("jit.trace", w0 + 0.1, w0 + 0.2)
        tr.record("jit.trace", w0 + 0.05, w0 + 0.4)
    total, own = bench_spans.span_seconds(tr.buffer.snapshot())
    # the inner trace is counted once in the total, and off its parent's
    # self time
    assert total["jit.trace"] == pytest.approx(0.35)
    assert own["jit.trace"] == pytest.approx(0.35)
    assert own["score"] == pytest.approx(total["score"] - 0.35)


def test_idle_by_span_labels_the_innermost_span_per_thread():
    hs = [("run_search", 0.0, 100.0, "main"),
          ("score", 10.0, 40.0, "main"),
          ("kernel.launch", 20.0, 30.0, "main"),
          ("pack", 35.0, 60.0, "prefetch")]
    idle = dict(bench_spans.idle_by_span([(0.0, 100.0), (120.0, 130.0)],
                                         hs))
    assert idle == pytest.approx({
        "run_search": 10e-9 + 40e-9, "score": 10e-9 + 5e-9,
        "kernel.launch": 10e-9, "pack+score": 5e-9,
        "pack+run_search": 20e-9, "no-span": 10e-9})


def test_unexplained_idle_share():
    ctx = {"span_s": {"fused.kernel-group": 2.0, "kernel.pack": 1.0,
                      "kernel.launch": 0.9},
           "main_phase_s": {"score": 2.5, "pack": 1.0},
           "queries": [{"wall_s": 4.0, "main_phase_s": {"score": 2.5,
                                                        "pack": 1.0}}]}
    c = bench_spans.checks(ctx, [["run_search", 0.1], ["no-span", 0.1],
                                 ["pack", 1.0]], 10.0)
    assert c["kernel_spans_over_group"] == pytest.approx(0.95)
    assert c["kernel_groups_over_score"] == pytest.approx(0.8)
    assert c["main_phase_over_wall"] == pytest.approx(0.875)
    assert c["unexplained_idle_pct"] == pytest.approx(2.0)


def test_host_spans_reads_only_host_planes():
    # two Python threads: one line name, two lines
    planes = [{"name": "/host:CPU", "lines": [
        {"name": "python", "events": [["pack", 5.0, 2.0],
                                      ["PjitFunction(f)", 5.5, 1.0]]},
        {"name": "python", "events": [["score", 4.0, 5.0]]}]},
        {"name": "/device:TPU:0", "lines": [
            {"name": trace_reduce.OPS_LINE, "events": [["pack", 1.0,
                                                        1.0]]}]}]
    hs = bench_spans.host_spans(planes, {"pack", "score"})
    assert hs == [("pack", 5.0, 7.0, "/host:CPU#0"),
                  ("score", 4.0, 9.0, "/host:CPU#1")]
    assert dict(bench_spans.idle_by_span([(4.0, 9.0)], hs)) == \
        pytest.approx({"pack+score": 2e-9, "score": 3e-9})
