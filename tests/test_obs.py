"""repro.obs tests: span nesting and export schemas, metrics, progress
events (ConsoleSink parity with the historical verbose output), cache
counter reconciliation on a real `run_search`, thread safety, run
manifests, and the zero-overhead-when-off contract."""
import json
import threading
import time

import numpy as np
import pytest

from repro.core import (Conv2D, FC, MapperConfig, Pool2D, TaskDescription,
                        generate_arch_space)
from repro.obs import (MANIFEST_DIR, NULL_TRACER, CollectSink, ConsoleSink,
                       Metrics, ProgressEvent, ProgressStream, RunManifest,
                       Span, TraceBuffer, Tracer, activate, as_stream,
                       as_tracer, current_tracer, family_of)
from repro.search import ResultCache, run_search

TASK = TaskDescription(
    name="tiny", input_shape=(8, 8, 3), batch_size=2,
    processing_type="Inference",
    layers=(Conv2D(8, (3, 3), (1, 1), (1, 1), name="c1"),
            Pool2D((2, 2), (2, 2), name="p1"),
            FC(10, name="fc")))
CFG = MapperConfig(max_mappings=200, seed=0)


def arch_list():
    return list(generate_arch_space(num_pes=(16, 64), rf_words=(64,),
                                    gbuf_words=(2048, 8192), bits=16))


# ---------------------------------------------------------------------------
# spans
# ---------------------------------------------------------------------------
def test_span_nesting_and_ordering():
    tr = Tracer()
    with tr.span("outer", phase=True, a=1):
        with tr.span("outer.mid") as mid:
            mid.set(rows=7)
            with tr.span("outer.leaf"):
                pass
        with tr.span("outer.mid2"):
            pass
    spans = tr.buffer.snapshot()
    assert [s.name for s in spans] == ["outer", "outer.mid", "outer.leaf",
                                       "outer.mid2"]
    by = {s.name: s for s in spans}
    assert by["outer"].parent is None and by["outer"].depth == 0
    assert by["outer.mid"].parent == by["outer"].index
    assert by["outer.leaf"].parent == by["outer.mid"].index
    assert by["outer.leaf"].depth == 2
    assert by["outer.mid2"].parent == by["outer"].index
    assert by["outer.mid"].attrs == {"rows": 7}
    for s in spans:
        assert s.t1 is not None and s.t1 >= s.t0
    # children are contained in their parents
    assert by["outer"].t0 <= by["outer.leaf"].t0
    assert by["outer.leaf"].t1 <= by["outer"].t1


def test_phase_times_counts_only_phase_spans():
    tr = Tracer()
    with tr.span("score", phase=True):
        with tr.span("backend.jnp"):        # nested detail: not counted
            time.sleep(0.01)
    with tr.span("score", phase=True):
        pass
    pt = tr.phase_times()
    assert set(pt) == {"score"}
    assert pt["score"] >= 0.01


def test_family_of():
    assert family_of("backend.jnp") == "backend"
    assert family_of("score") == "score"
    assert family_of("fused.kernel-group") == "fused"


def test_jsonl_round_trip(tmp_path):
    tr = Tracer()
    with tr.span("a", phase=True, k="v"):
        with tr.span("a.b"):
            pass
    tr.metrics.counter("hits").inc(2)
    tr.metrics.counter("hits").inc(1)
    path = tr.export_jsonl(str(tmp_path / "t.jsonl"))
    text = open(path).read()
    lines = [json.loads(l) for l in text.splitlines()]
    assert "meta" in lines[0] and lines[0]["meta"]["n_spans"] == 2
    # the last line is the tracer's Metrics snapshot
    assert lines[-1]["metrics"] == tr.metrics.snapshot()
    assert lines[-1]["metrics"]["counters"] == {"hits": 3}
    buf2 = TraceBuffer.from_jsonl(text)
    assert len(buf2.snapshot()) == 2
    assert buf2.phase_times() == tr.phase_times()
    s0, s1 = buf2.snapshot()
    assert s0.name == "a" and s0.phase and s0.attrs == {"k": "v"}
    assert s1.parent == s0.index


def test_chrome_trace_schema(tmp_path):
    tr = Tracer()
    with tr.span("score", phase=True, rows=4):
        with tr.span("backend.jnp"):
            pass
    tr.metrics.counter("cache.hits").inc(5)
    path = tr.export_chrome(str(tmp_path / "t.json"))
    with open(path) as f:
        ct = json.load(f)
    evs = ct["traceEvents"]
    metas = [e for e in evs if e["ph"] == "M"]
    xs = [e for e in evs if e["ph"] == "X"]
    cs = [e for e in evs if e["ph"] == "C"]
    lane_names = {e["args"]["name"] for e in metas
                  if e["name"] == "thread_name"}
    assert {"score", "backend"} <= lane_names
    assert len(xs) == 2
    for e in xs:
        assert e["ts"] >= 0 and e["dur"] >= 0 and e["pid"] == 0
    by = {e["name"]: e for e in xs}
    assert by["score"]["cat"] == "phase"
    assert by["score"]["args"]["rows"] == 4
    assert by["backend.jnp"]["cat"] == "detail"
    # spans in different families land in different lanes
    assert by["score"]["tid"] != by["backend.jnp"]["tid"]
    assert cs and cs[0]["name"] == "cache.hits" \
        and cs[0]["args"]["value"] == 5
    # counters are stamped at the buffer's last span end, not at export
    end = max(e["ts"] + e["dur"] for e in xs)
    assert cs[0]["ts"] == pytest.approx(end, abs=1e-3)


def test_record_nests_finished_intervals():
    tr = Tracer()
    w0 = tr.buffer.t_wall0
    with tr.span("outer"):
        inner = tr.record("jit.trace", w0 + 0.2, w0 + 0.3, fun="inner")
        outer = tr.record("jit.trace", w0 + 0.1, w0 + 0.5, fun="outer")
        after = tr.record("jit.lower", w0 + 0.6, w0 + 0.7, fun="outer")
    spans = {s.index: s for s in tr.buffer.snapshot()}
    root = next(s for s in spans.values() if s.name == "outer")
    # wall-clock times land on the buffer's perf_counter clock
    assert outer.t0 == pytest.approx(tr.buffer.t_perf0 + 0.1)
    assert outer.duration == pytest.approx(0.4)
    # an interval recorded earlier inside a later one becomes its child
    assert inner.parent == outer.index
    assert outer.parent == root.index and after.parent == root.index


def test_jit_events_become_spans_of_the_open_span():
    import jax
    import jax.numpy as jnp

    def obs_fresh_fn(x):
        return x * 3.0 - 1.0

    f = jax.jit(obs_fresh_fn)
    x = jnp.arange(8.0)
    f(x + 1.0)          # NULL_TRACER ambient: the events go nowhere
    g = jax.jit(lambda x: obs_fresh_fn(x) + 0.5)
    tr = Tracer()
    with activate(tr), tr.span("outer"):
        g(x).block_until_ready()
    spans = tr.buffer.snapshot()
    outer = next(s for s in spans if s.name == "outer")
    mine = [s for s in spans if s.name.startswith("jit.")
            and s.attrs["fun"] == "<lambda>"]
    assert sorted(s.name for s in mine) == ["jit.compile", "jit.lower",
                                           "jit.trace"]
    assert all(s.parent == outer.index for s in mine)
    assert all(outer.t0 - 1e-3 <= s.t0 <= s.t1 <= outer.t1 + 1e-3
               for s in mine)
    # a second call finds the program in jit's cache: no new events
    n = len(tr.buffer)
    with activate(tr), tr.span("again"):
        g(x).block_until_ready()
    assert not [s for s in tr.buffer.snapshot()[n:]
                if s.name.startswith("jit.")]


def test_spans_record_the_thread_role():
    tr = Tracer()
    with activate(tr, role="main"):
        with tr.span("pack", phase=True):
            time.sleep(0.002)

        def work():
            with activate(tr, role="prefetch"):
                with tr.span("pack", phase=True):
                    time.sleep(0.002)
        t = threading.Thread(target=work)
        t.start()
        t.join()
    with tr.span("score", phase=True):
        pass
    by_role = tr.buffer.phase_times_by_role()
    assert set(by_role) == {"main", "prefetch", "other"}
    assert set(by_role["main"]) == {"pack"} and by_role["main"]["pack"] > 0
    assert tr.phase_times()["pack"] == pytest.approx(
        by_role["main"]["pack"] + by_role["prefetch"]["pack"])
    assert [s.role for s in tr.buffer.snapshot()] == ["main", "prefetch",
                                                       None]


def test_null_tracer_and_as_tracer():
    assert NULL_TRACER.span("x") is NULL_TRACER.span("y")   # shared no-op
    with NULL_TRACER.span("x") as s:
        assert s.set(a=1) is s
    assert NULL_TRACER.phase_times() == {}
    assert as_tracer(None) is NULL_TRACER       # no ambient by default
    assert as_tracer(False) is NULL_TRACER
    assert as_tracer(True).enabled
    tr = Tracer()
    assert as_tracer(tr) is tr
    with pytest.raises(TypeError):
        as_tracer("yes")
    # activation scopes the ambient tracer
    assert current_tracer() is NULL_TRACER
    with activate(tr):
        assert current_tracer() is tr
        assert as_tracer(None) is tr
        assert as_tracer(False) is NULL_TRACER  # explicit off wins
    assert current_tracer() is NULL_TRACER


def test_noop_span_overhead_smoke():
    n = 50_000
    t0 = time.perf_counter()
    for _ in range(n):
        with NULL_TRACER.span("x"):
            pass
    per_call = (time.perf_counter() - t0) / n
    assert per_call < 20e-6         # generous CI bound; typical ~0.2us


def test_tracer_thread_safety():
    tr = Tracer()
    n_threads, n_spans = 8, 200

    def work(tid):
        for i in range(n_spans):
            with tr.span(f"t{tid}.outer", phase=(i % 2 == 0)):
                with tr.span(f"t{tid}.inner"):
                    pass

    threads = [threading.Thread(target=work, args=(t,))
               for t in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    spans = tr.buffer.snapshot()
    assert len(spans) == n_threads * n_spans * 2
    assert all(s.t1 is not None for s in spans)
    by_index = {s.index: s for s in spans}
    for s in spans:
        # nesting never crosses threads
        if s.parent is not None:
            assert by_index[s.parent].thread == s.thread
        if s.name.endswith(".inner"):
            assert by_index[s.parent].name == s.name.split(".")[0] \
                + ".outer"
    pt = tr.phase_times()
    assert len(pt) == n_threads     # one phase name per thread


# ---------------------------------------------------------------------------
# metrics
# ---------------------------------------------------------------------------
def test_metrics_registry():
    m = Metrics()
    m.counter("c").inc()
    m.counter("c").inc(2)
    m.gauge("g").set(7)
    for v in range(1, 101):
        m.histogram("h").observe(v)
    snap = m.snapshot()
    assert snap["counters"]["c"] == 3
    assert snap["gauges"]["g"] == 7.0
    h = snap["histograms"]["h"]
    assert h["count"] == 100 and h["min"] == 1 and h["max"] == 100
    assert 49 <= h["p50"] <= 52 and 94 <= h["p95"] <= 97
    assert h["mean"] == pytest.approx(50.5)
    assert json.dumps(snap)         # JSON-safe
    assert m.histogram("empty").snapshot() == {"count": 0}


# ---------------------------------------------------------------------------
# progress events
# ---------------------------------------------------------------------------
def test_progress_stream_and_sinks():
    st = ProgressStream()
    assert not st.active
    st.emit("round-finished", round=1)      # no sinks: no-op
    sink = CollectSink()
    st.subscribe(sink)
    assert st.active
    st.emit("frontier-grew", arch="a", size=2)
    assert len(sink.events) == 1
    ev = sink.events[0]
    assert ev.kind == "frontier-grew" and ev.payload["size"] == 2
    assert ev.to_dict()["arch"] == "a"
    # normalization
    assert as_stream(st) is st
    assert as_stream(None).sinks == []
    assert as_stream(sink).sinks == [sink]
    assert as_stream([sink, sink]).sinks == [sink, sink]


def test_console_sink_renders_historical_format(capsys):
    sink = ConsoleSink()
    sink(ProgressEvent("arch-evaluated", 0.0,
                       {"arch": "pe64_rf64_gb2048", "cycles": 1.5e6,
                        "energy_pj": 2.5e9, "edp": 3.75e15,
                        "feasible": True}))
    sink(ProgressEvent("arch-evaluated", 0.0,
                       {"arch": "pe16_rf64_gb2048", "cycles": 1e6,
                        "energy_pj": 2e9, "edp": 2e15, "feasible": False}))
    sink(ProgressEvent("arch-skipped", 0.0,
                       {"arch": "pe16_rf64_gb2048", "violation": 0.25}))
    sink(ProgressEvent("round-finished", 0.0, {"round": 1}))  # silent
    out = capsys.readouterr().out.splitlines()
    assert out == [
        "  pe64_rf64_gb2048             cycles=1.500e+06 "
        "energy=2.500e+09pJ edp=3.750e+15",
        "  pe16_rf64_gb2048             cycles=1.000e+06 "
        "energy=2.000e+09pJ edp=2.000e+15  [infeasible]",
        "  pe16_rf64_gb2048             statically infeasible "
        "(violation 0.250)",
    ]


# ---------------------------------------------------------------------------
# run_search integration: reconciliation, events, manifest, summary
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def traced_run(tmp_path_factory):
    cache_dir = str(tmp_path_factory.mktemp("obs_cache"))
    sink = CollectSink()
    rep = run_search(TASK, arch_list(), goal="edp", cfg=CFG, trace=True,
                     progress=sink, cache=cache_dir)
    return rep, sink, cache_dir


def test_counter_reconciliation_against_cache_stats(traced_run):
    rep, _, cache_dir = traced_run
    # CacheStats is the one source of truth: the report's hit/miss
    # counters ARE the stats delta, and the split adds up
    cs = rep.cache_stats
    assert rep.n_cache_hits == cs["hits_memory"] + cs["hits_disk"]
    assert rep.n_cache_misses == cs["misses"]
    assert rep.n_enumerations == rep.n_cache_misses
    assert cs["puts"] == cs["misses"]
    s = rep.summary()
    assert s["cache"] == cs
    assert s["n_cache_hits"] == rep.n_cache_hits
    # a second run over the same disk cache is served entirely from it
    rep2 = run_search(TASK, arch_list(), goal="edp", cfg=CFG,
                      cache=cache_dir)
    assert rep2.n_enumerations == 0
    assert rep2.n_cache_misses == 0
    assert rep2.n_cache_hits == rep.n_cache_hits + rep.n_cache_misses
    assert rep2.cache_stats["hits_memory"] \
        + rep2.cache_stats["hits_disk"] == rep2.n_cache_hits
    assert rep2.goal_value() == rep.goal_value()


def test_phase_times_cover_run(traced_run):
    rep, _, _ = traced_run
    assert rep.phase_times, "tracing on -> phase accounting"
    roots = [sp for sp in rep.tracer.buffer.snapshot()
             if sp.name == "run_search"]
    assert len(roots) == 1
    cov = sum(rep.phase_times.values()) / roots[0].duration
    assert cov >= 0.8, f"phase spans cover only {cov:.1%}"
    assert {"propose", "score", "frontier-update"} <= set(rep.phase_times)
    assert rep.summary()["phase_times"] == rep.phase_times
    assert 0 < rep.wall_time_s
    assert rep.summary()["metrics"]["counters"]["search.rounds"] >= 1


def test_progress_events_reconcile_with_report(traced_run):
    rep, sink, _ = traced_run
    assert len(sink.of("arch-evaluated")) == rep.n_evaluated \
        - rep.n_skipped_infeasible
    assert len(sink.of("search-finished")) == 1
    fin = sink.of("search-finished")[0].payload
    assert fin["best_arch"] == rep.best.hardware.name
    assert fin["n_evaluated"] == rep.n_evaluated
    lookups = sink.of("cache-lookup")
    assert len(lookups) == rep.n_cache_hits + rep.n_cache_misses
    assert sum(1 for e in lookups if not e.payload["hit"]) \
        == rep.n_cache_misses
    assert len(sink.of("frontier-grew")) >= 1


def test_manifest_written_and_round_trips(traced_run):
    rep, _, cache_dir = traced_run
    assert rep.manifest_path is not None
    assert MANIFEST_DIR in rep.manifest_path
    m = RunManifest.read(rep.manifest_path)
    assert m.run_id == rep.manifest.run_id
    assert m.best_arch == rep.best.hardware.name
    assert m.counters["n_evaluated"] == rep.n_evaluated
    assert m.counters["cache"] == rep.cache_stats
    assert m.space_digest and m.backend == rep.backend
    assert m.phase_times.keys() == rep.phase_times.keys()
    # the device stamp names what JAX scored on, and survives the trip
    import jax
    dev = jax.local_devices()
    assert (m.device_platform, m.device_kind, m.device_count) == \
        (dev[0].platform, dev[0].device_kind, len(dev))
    assert m.jax_backend == jax.default_backend()
    # manifests live outside the GC-swept cache root
    cache = ResultCache(path=cache_dir, max_disk_entries=0)
    evicted = cache.gc()
    assert evicted > 0
    assert RunManifest.read(rep.manifest_path).run_id == m.run_id


def test_verbose_output_unchanged_by_event_refactor(capsys):
    rep = run_search(TASK, arch_list(), goal="edp", cfg=CFG, verbose=True)
    out = capsys.readouterr().out.splitlines()
    lines = [l for l in out if l.startswith("  ")]
    assert len(lines) == rep.n_evaluated
    for res, line in zip(rep.all_archs, lines):
        n = res.network
        assert line == (f"  {res.hardware.name:28s} "
                        f"cycles={n.cycles:.3e} "
                        f"energy={n.energy_pj:.3e}pJ edp={n.edp:.3e}")


def test_trace_off_records_nothing():
    rep = run_search(TASK, arch_list(), goal="edp", cfg=CFG, trace=False)
    assert rep.tracer is None
    assert rep.phase_times == {}
    assert rep.summary()["metrics"] is None
    # cache reconciliation still works without tracing
    assert rep.n_enumerations == rep.n_cache_misses
    assert rep.cache_stats is not None


def test_ambient_tracer_captures_library_spans():
    tr = Tracer()
    with activate(tr):
        run_search(TASK, arch_list()[:2], goal="edp", cfg=CFG)
    names = {s.name for s in tr.buffer.snapshot()}
    assert "run_search" in names
    assert "pack" in names and "validate" in names and "score" in names
