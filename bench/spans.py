#!/usr/bin/env python3
"""The program's own spans of a DSE query, per query and on the
profiler's clock.

    python3 bench/spans.py --workload <cell> --seed <n> --queries <k>

After the set-up `bench/run.py` makes (compile cache, device check, one
warm-up query) it runs k untraced queries, k traced ones, and one more
traced query under the JAX profiler, and prints:

  tracing cost:       seconds per query untraced and traced, and the
                      difference (what recording spans costs);
  program per query:  per-query means over the traced queries of each
                      span's self time (its duration less its children's),
                      the `main` thread's phases, the tracer's counters,
                      the no-bypass rows the jnp scorer took, and each
                      query's own phase seconds;
  idle by span:       the profiled query's device idle time, labelled
                      with the innermost program span open on each host
                      thread (`host_spans`: the spans' TraceAnnotations in
                      the profile's host plane);

and last one JSON object: the per-layer metrics of `bench/metrics/` that
read program spans (`SPAN_METRICS`), and the checks of the spans'
coverage.  The readers read three fields a `bench.run.Context` does not
have yet (`span_s`, `main_phase_s`, `counters`); `program_context`
computes them.
"""
from __future__ import annotations

import argparse
import json
import shutil
import sys
import tempfile
import time
from collections import defaultdict
from pathlib import Path
from typing import Dict, List, Sequence, Tuple

ROOT = Path(__file__).resolve().parents[1]
for _p in (ROOT / "src", ROOT):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

from bench import trace_reduce                                 # noqa: E402
from bench.cell import CellError, resolve                      # noqa: E402
from bench.compile_stats import row_counts                     # noqa: E402
from bench.query import make_query                             # noqa: E402
from bench.run import (PEAKS, Context, _layout, _profiled,     # noqa: E402
                       enable_cache, say)
from repro.obs import family_of                                # noqa: E402

SPAN_METRICS = ("kernel_pack_s", "kernel_transfer_s", "kernel_launch_s",
                "jit_s", "build_wait_s")
KERNEL_SPANS = ("kernel.pack", "kernel.transfer", "kernel.launch",
                "kernel.fetch", "kernel.select")
#: idle time under these labels is time the spans do not explain
UNEXPLAINED = ("run_search", "no-span")


def span_seconds(spans) -> Tuple[Dict[str, float], Dict[str, float]]:
    """(total, self) seconds by span name.  A span inside another of its
    family (a JAX trace inside a trace) is left out of `total`, which is
    then the time a span of that name was open; `self` is each span's
    duration less its direct children's."""
    by_index = {s.index: s for s in spans}
    child_s: Dict[int, float] = defaultdict(float)
    for s in spans:
        if s.t1 is not None and s.parent is not None:
            child_s[s.parent] += s.duration
    total: Dict[str, float] = defaultdict(float)
    own: Dict[str, float] = defaultdict(float)
    for s in spans:
        if s.t1 is None:
            continue
        own[s.name] += s.duration - child_s.get(s.index, 0.0)
        p = by_index.get(s.parent)
        if p is None or family_of(p.name) != family_of(s.name):
            total[s.name] += s.duration
    return dict(total), dict(own)


def _mean(dicts: Sequence[Dict[str, float]]) -> Dict[str, float]:
    out: Dict[str, float] = defaultdict(float)
    for d in dicts:
        for k, v in d.items():
            out[k] += v / len(dicts)
    return dict(out)


def program_context(traced: Sequence[Tuple[object, float]]):
    """Per-query means over traced queries, each given as (its tracer,
    its wall seconds): -> dict with `span_s` (total seconds by span
    name), `self_s`, `main_phase_s` (the `main` thread's phases),
    `counters`, `eligible_rows` and per query `queries`."""
    totals, selfs, mains, counters, queries = [], [], [], [], []
    eligible = 0
    for tracer, wall in traced:
        spans = tracer.buffer.snapshot()
        t, o = span_seconds(spans)
        totals.append(t)
        selfs.append(o)
        main = tracer.buffer.phase_times_by_role().get("main", {})
        mains.append(main)
        counters.append(tracer.metrics.snapshot()["counters"])
        eligible += sum(s.attrs.get("eligible_rows", 0) for s in spans)
        queries.append({"wall_s": wall, "main_phase_s": main})
    n = max(len(traced), 1)
    return {"span_s": _mean(totals), "self_s": _mean(selfs),
            "main_phase_s": _mean(mains), "counters": _mean(counters),
            "eligible_rows": eligible / n, "queries": queries}


def host_spans(planes: Sequence[dict], names) -> List[Tuple[str, float,
                                                             float, str]]:
    """The host-plane events whose names are program span names:
    [(name, start_ns, end_ns, thread line), ...].  A line is one thread;
    Python threads share one line name, so the line is named by its
    plane and position ("/host:CPU#0")."""
    names = set(names)
    out = []
    for pl in planes:
        if not pl["name"].startswith("/host:"):
            continue
        for k, ln in enumerate(pl["lines"]):
            for name, t0, dur in ln["events"]:
                if name in names:
                    out.append((name, t0, t0 + dur, f"{pl['name']}#{k}"))
    return out


def idle_by_span(gaps: Sequence[Tuple[float, float]], spans,
                 top: int = 16) -> List[List]:
    """Idle seconds by the innermost program span open on each host
    thread: every gap is cut at the spans' edges and each piece labelled
    with the innermost span per thread (the one opened last), joined by
    "+" ("no-span" when none is open).  -> [[label, seconds], ...]."""
    by_label: Dict[str, float] = defaultdict(float)
    for a, b in gaps:
        inside = [s for s in spans if s[1] < b and s[2] > a]
        cuts = sorted({a, b} | {min(max(x, a), b)
                                for s in inside for x in s[1:3]})
        for lo, hi in zip(cuts, cuts[1:]):
            inner: Dict[str, Tuple[float, str]] = {}
            for name, s0, s1, line in inside:
                if s0 <= lo and hi <= s1 and (line not in inner
                                             or s0 >= inner[line][0]):
                    inner[line] = (s0, name)
            label = "+".join(sorted(n for _, n in inner.values()))
            by_label[label or "no-span"] += (hi - lo) * 1e-9
    return [[k, v] for k, v in sorted(by_label.items(),
                                      key=lambda kv: -kv[1])[:top]]


def plane_agreement(spans, hs, t0_ns: float, t_enter: float) -> float:
    """Widest gap in seconds between a phase span's duration as the
    tracer recorded it and as the profile's host plane holds it, and
    between its start mapped from `perf_counter` (as `bench/run.py`'s
    `_host_phases` maps it) and its start in the plane."""
    plane = defaultdict(list)
    for name, s0, s1, _ in sorted(hs, key=lambda h: h[1]):
        plane[name].append((s0, s1))
    mine = defaultdict(list)
    for s in sorted(spans, key=lambda s: s.t0):
        if s.phase and s.t1 is not None:
            mine[s.name].append(s)
    worst = 0.0
    for name, ss in mine.items():
        ps = plane.get(name, [])
        if len(ps) != len(ss):
            return float("inf")
        for s, (p0, p1) in zip(ss, ps):
            worst = max(worst, abs(s.duration - (p1 - p0) * 1e-9),
                        abs(t0_ns + (s.t0 - t_enter) * 1e9 - p0) * 1e-9)
    return worst


def checks(ctx: dict, idle, window_s: float) -> dict:
    """The spans' coverage: kernel spans over kernel groups, kernel
    groups over `score`, the main thread's phases over the query's wall
    time, and the idle share the spans leave unexplained."""
    s, m = ctx["span_s"], ctx["main_phase_s"]
    group = s.get("fused.kernel-group", 0.0)
    out = {"main_phase_over_wall": max(
        (sum(q["main_phase_s"].values()) / q["wall_s"]
         for q in ctx["queries"]), default=None)}
    if group:
        out["kernel_spans_over_group"] = sum(
            s.get(k, 0.0) for k in KERNEL_SPANS) / group
        out["kernel_groups_over_score"] = group / m["score"]
    if window_s > 0:
        out["unexplained_idle_pct"] = 100.0 * sum(
            v for k, v in idle if k in UNEXPLAINED) / window_s
    return out


def parse_args(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--queries", type=int, default=3)
    return ap.parse_args(argv)


def main(argv=None, *, root: Path = ROOT, require_tpu: bool = True) -> int:
    args = parse_args(argv)
    try:
        cell = resolve(args.workload, root)
    except CellError as e:
        print(f"spans: {e}", file=sys.stderr)
        return 2
    enable_cache(root)
    import jax
    d0 = jax.devices()[0]
    if require_tpu and d0.platform != "tpu":
        print(f"spans: no TPU: JAX found platform {d0.platform!r}",
              file=sys.stderr)
        return 2
    peaks = json.loads(PEAKS.read_text()).get(d0.device_kind)
    query = make_query(cell.config, cell.traffic, args.seed)
    query(trace=True)                                   # warm-up

    def timed(trace):
        t = time.perf_counter()
        rep = query(trace=trace)
        return rep, time.perf_counter() - t

    untraced = [timed(None)[1] for _ in range(args.queries)]
    traced = []
    for _ in range(args.queries):
        rep, wall = timed(True)
        traced.append((rep.tracer, wall))
        del rep
    cost = (sum(w for _, w in traced) - sum(untraced)) / args.queries
    say(f"tracing cost: untraced_s={json.dumps(untraced)} "
        f"traced_s={json.dumps([w for _, w in traced])} "
        f"cost_s={cost!r}")

    ctx = program_context(traced)
    say("program per query: " + json.dumps({
        "self_s": dict(sorted(ctx["self_s"].items(),
                              key=lambda kv: -kv[1])),
        "main_phase_s": ctx["main_phase_s"],
        "counters": ctx["counters"],
        "eligible_rows": ctx["eligible_rows"],
        "queries": ctx["queries"]}))

    log_dir = tempfile.mkdtemp(prefix="bench-spans-")
    try:
        rep0, planes, (t0_ns, t1_ns), t_enter = _profiled(query, log_dir)
    finally:
        shutil.rmtree(log_dir, ignore_errors=True)
    spans0 = rep0.tracer.buffer.snapshot()
    opened = [s for s in spans0 if not s.name.startswith("jit.")]
    hs = host_spans(planes, {s.name for s in opened})
    win = trace_reduce.reduce_window(planes, t0_ns, t1_ns)
    idle = idle_by_span(win.gaps, hs)
    say("idle by span: " + json.dumps(idle))

    rctx = Context({}, *row_counts(rep0.tracer), win, _layout(cell.config),
                   peaks)
    rctx.span_s = ctx["span_s"]
    rctx.main_phase_s = ctx["main_phase_s"]
    rctx.counters = ctx["counters"]
    metrics = {}
    for name in SPAN_METRICS:
        v = cell.reader(name)(rctx)
        if v is not None:
            metrics[name] = v
    out = {"metrics": metrics,
           "checks": {**checks(ctx, idle, win.window_s),
                      "window_s": win.window_s, "busy_s": win.busy_s,
                      "plane_agreement_s": plane_agreement(
                          spans0, hs, t0_ns, t_enter),
                      "host_spans": len(hs), "spans": len(opened)},
           "tracing_cost_s": cost,
           "device": {"platform": d0.platform, "kind": d0.device_kind}}
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
