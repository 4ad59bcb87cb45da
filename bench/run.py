#!/usr/bin/env python3
"""Benchmark of the TRIM design-space explorer: one DSE query, back to back.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell of BENCHMARK.json names a configuration (a network and a lattice
of hardware designs, `bench/configs/`) and a traffic (the mapper and
search options of the query, `bench/traffic/`).  One run:

  set-up   turns on JAX's persistent compilation cache in the checkout's
           `.jax_cache`, checks the device (no TPU, or fewer chips than
           the cell asks for: exit 2 and no result), analyses the network
           and runs one warm-up query, which compiles or loads every
           program the window uses;
  window   issues the same query (`repro.search.run_search`, mapper seed
           `--seed`, a fresh lattice and result cache each time) while
           fewer than `--seconds` have passed; the last one runs to its
           end.  `query_s` is the window's seconds over its queries;
  trace    with `--trace 1` every query records the program's spans and
           the first one of the window runs under the JAX profiler; the
           per-layer metrics (`bench/metrics/<name>.py`) read both;
  check    after the window the answers are judged by the plain reference
           (`bench/reference/check.py`) against `bench/reference/limits.json`.

Earlier lines give the rows per query by scoring path, the compiles and
compile-cache traffic inside the window and the seconds per driver phase.
The last line of standard output is one JSON object: `correct`,
`attempted`, `failed`, `metrics`, `device`, with `--trace 1` `breakdown`,
and last `compared`, each number compared beside its limit.  The same
numbers are the last lines of standard error.
"""
from __future__ import annotations

import argparse
import gc
import hashlib
import json
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
for _p in (ROOT / "src", ROOT):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

from bench.cell import CellError, resolve                      # noqa: E402
from bench.compile_stats import CompileStats, row_counts       # noqa: E402
from bench.query import answers, make_query                    # noqa: E402
from bench import trace_reduce                                 # noqa: E402

ANNOTATION = "bench.query"
PEAKS = Path(__file__).resolve().parent / "peaks.json"
CACHE_DIR = ".jax_cache"


def enable_cache(root: Path) -> str:
    """JAX's persistent compilation cache at the fixed `<root>/.jax_cache`,
    inside the checkout whatever the environment names, so that two
    checkouts share no compiled program and only a cell's first run in a
    checkout compiles.  Every compile is cached, however short: the
    mapspace kernels compile in well under JAX's default threshold.  The
    cache is never pruned: a size limit from the environment turns on
    JAX's eviction, whose scan fails every write once one entry's access
    time is missing, and the window then compiles.  -> the directory."""
    import jax
    path = str(root / CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_compilation_cache_max_size", -1)
    return path


class GcClock:
    """Seconds the garbage collector runs, and its full collections,
    from `gc.callbacks`."""

    def __init__(self):
        self.seconds, self.full, self._t0 = 0.0, 0, None
        gc.callbacks.append(self._note)

    def _note(self, phase, info):
        if phase == "start":
            self._t0 = time.perf_counter()
        elif self._t0 is not None:
            self.seconds += time.perf_counter() - self._t0
            self.full += info["generation"] == 2
            self._t0 = None

    def snapshot(self):
        return self.seconds, self.full

    def close(self):
        gc.callbacks.remove(self._note)


def process_age_s() -> float:
    """Seconds since this process started, from the kernel's records."""
    with open("/proc/self/stat") as f:
        start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
    with open("/proc/uptime") as f:
        uptime = float(f.read().split()[0])
    return uptime - start_ticks / os.sysconf("SC_CLK_TCK")


def parse_args(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def say(*parts) -> None:
    print(*parts, flush=True)


class Context:
    """What the per-layer metric readers read: per-query phase seconds,
    rows per scoring path, the device trace of one query, the level
    layout of the rows and the device's peaks."""

    def __init__(self, phase_s, kernel_rows, jnp_rows, device, layout,
                 peaks):
        self.phase_s = phase_s            # mean seconds per query
        self.kernel_rows = kernel_rows    # rows per query, kernel path
        self.jnp_rows = jnp_rows          # rows per query, jnp path
        self.device = device              # trace_reduce.DeviceWindow
        self.layout = layout              # (tiling levels, memories)
        self.peaks = peaks                # or None: device not traced
        self.notes = {}                   # readers' remarks, printed

    def phases(self, names) -> float:
        return sum(self.phase_s.get(n, 0.0) for n in names)


def _fingerprint(ans) -> str:
    return hashlib.sha256(json.dumps(ans, sort_keys=True, default=str)
                          .encode()).hexdigest()


def _layout(config):
    from bench.reference.check import reference_designs
    hw = reference_designs(config)[0]
    return len(hw.tiling_levels), len(hw.memory_level_indices())


def _profiled(query, log_dir):
    """One query under the JAX profiler; -> (report, planes, window in
    trace ns, perf_counter at the window's start)."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        t_enter = time.perf_counter()
        with jax.profiler.TraceAnnotation(ANNOTATION):
            report = query(trace=True)
    finally:
        jax.profiler.stop_trace()
    planes = trace_reduce.from_profile(log_dir)
    t0_ns, t1_ns = trace_reduce.find_event(planes, ANNOTATION)
    return report, planes, (t0_ns, t1_ns), t_enter


def _host_phases(report, t0_ns, t_enter):
    """The query's phase spans on the trace's clock."""
    return [(s.name, t0_ns + (s.t0 - t_enter) * 1e9,
             t0_ns + (s.t1 - t_enter) * 1e9)
            for s in report.tracer.buffer.snapshot()
            if s.phase and s.t1 is not None]


def main(argv=None, *, root: Path = ROOT, require_tpu: bool = True) -> int:
    t_start = time.perf_counter() - process_age_s()
    args = parse_args(argv)
    try:
        cell = resolve(args.workload, root)
    except CellError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2

    t_main = time.perf_counter()
    cache_dir = enable_cache(root)
    t_jax = time.perf_counter()
    import jax
    devs = jax.devices()
    t_devs = time.perf_counter()
    d0 = devs[0]
    if require_tpu and d0.platform != "tpu":
        print(f"bench: no TPU: JAX found platform {d0.platform!r} "
              f"({d0.device_kind}, {len(devs)} device(s))", file=sys.stderr)
        return 2
    if len(devs) < cell.chips:
        print(f"bench: cell {cell.name} needs {cell.chips} chips, JAX found "
              f"{len(devs)} ({d0.platform})", file=sys.stderr)
        return 2
    peaks_table = json.loads(PEAKS.read_text())
    peaks = peaks_table.get(d0.device_kind)
    if d0.platform == "tpu" and peaks is None:
        print(f"bench: device kind {d0.device_kind!r} is not in "
              f"{PEAKS.name}", file=sys.stderr)
        return 2
    say(f"device platform={d0.platform} kind={d0.device_kind} "
        f"count={len(devs)} compile_cache={cache_dir}")
    comp = CompileStats()

    # -- set-up: analyse the network, one warm-up query ---------------------
    query = make_query(cell.config, cell.traffic, args.seed)
    t_query = time.perf_counter()
    warm = query(trace=True)
    t_warm = time.perf_counter()
    _, n_compiles, n_hits, _ = comp.snapshot()
    say(f"setup: start_to_main_s={t_main - t_start!r} "
        f"import_jax_s={t_jax - t_main!r} jax_devices_s={t_devs - t_jax!r} "
        f"analyse_s={t_query - t_devs!r} warmup_s={t_warm - t_query!r} "
        f"warmup_compiles={n_compiles} warmup_cache_hits={n_hits}")
    kern, jnp_ = row_counts(warm.tracer)
    say(f"rows per query: kernel={kern} jnp={jnp_} "
        f"mapspaces={warm.n_enumerations} designs={warm.n_evaluated} "
        f"backend={warm.backend} overlap={warm.overlap}")
    if not cell.traffic["mapper"].get("enable_bypass", True) \
            and d0.platform == "tpu" and (jnp_ > 0 or kern == 0):
        print(f"bench: a no-bypass query must score every row with the "
              f"kernel: kernel={kern} jnp={jnp_}", file=sys.stderr)
        return 3
    del warm

    # -- window ---------------------------------------------------------------
    log_dir = tempfile.mkdtemp(prefix="bench-trace-") if args.trace else None
    outs, phase_sum, profile, q_s, gc_s = [], {}, None, [], []
    gcc = GcClock()
    c0, g0 = comp.snapshot(), gcc.snapshot()
    t_w0 = time.perf_counter()
    setup_s = t_w0 - t_start
    try:
        while True:
            t_q, g_q = time.perf_counter(), gcc.seconds
            if args.trace and profile is None:
                rep, planes, span, t_enter = _profiled(query, log_dir)
                profile = (rep, planes, span, t_enter)
            else:
                rep = query(trace=True if args.trace else None)
            q_s.append(time.perf_counter() - t_q)
            gc_s.append(gcc.seconds - g_q)
            outs.append(answers(rep))
            for k, v in rep.phase_times.items():
                phase_sum[k] = phase_sum.get(k, 0.0) + v
            del rep
            if time.perf_counter() - t_w0 >= args.seconds:
                break
        t_w1 = time.perf_counter()
    finally:
        if log_dir:
            shutil.rmtree(log_dir, ignore_errors=True)
    c1, g1 = comp.snapshot(), gcc.snapshot()
    gcc.close()
    n = len(outs)
    query_s = (t_w1 - t_w0) / n
    say(f"window: queries={n} seconds={t_w1 - t_w0!r} query_s={query_s!r} "
        f"setup_s={setup_s!r} compiles={c1[1] - c0[1]} "
        f"compile_s={c1[0] - c0[0]!r} compile_cache_hits={c1[2] - c0[2]} "
        f"compile_cache_misses={c1[3] - c0[3]} "
        f"gc_s={g1[0] - g0[0]!r} gc_full={g1[1] - g0[1]} "
        f"per_query_s={json.dumps(q_s)} per_query_gc_s={json.dumps(gc_s)}")
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devs[:max(cell.chips, 1)])

    # -- per-layer metrics (traced runs) --------------------------------------
    metrics, breakdown, device_extra = {}, None, {}
    if args.trace:
        phase_s = {k: v / n for k, v in phase_sum.items()}
        say("phase_s per query: " + json.dumps(dict(sorted(
            phase_s.items(), key=lambda kv: -kv[1]))))
        rep0, planes, (t0_ns, t1_ns), t_enter = profile
        win = trace_reduce.reduce_window(planes, t0_ns, t1_ns)
        pk, pj = row_counts(rep0.tracer)
        ctx = Context(phase_s, pk, pj, win, _layout(cell.config), peaks)
        for m in cell.per_layer:
            v = cell.reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        if ctx.notes:
            say("notes: " + json.dumps(ctx.notes))
        breakdown = {
            "device_ops": trace_reduce.top_ops(win),
            "idle_gaps": trace_reduce.label_gaps(
                win.gaps, _host_phases(rep0, t0_ns, t_enter))}
        device_extra = {"busy_s": win.busy_s, "window_s": win.window_s}
        say(f"trace: window_s={win.window_s!r} busy_s={win.busy_s!r} "
            f"devices={win.n_devices} kernel_rows={pk} jnp_rows={pj}")
        del planes, profile, rep0
    else:
        values = {"query_s": query_s, "setup_s": setup_s}
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}

    # -- correctness: the reference judges the last query's answers ---------
    ans = outs[-1]
    last = _fingerprint(ans)
    mismatched = sum(_fingerprint(a) != last for a in outs)
    del outs
    gc.collect()
    from bench.reference.check import Reference, judge, load_limits
    t_ref = time.perf_counter()
    nums = Reference(cell.config, cell.traffic, args.seed).compare(ans)
    nums["query_mismatch"] = mismatched
    limits = load_limits()
    ok, lines = judge(nums, limits)
    say(f"reference: seconds={time.perf_counter() - t_ref!r} "
        f"best={ans['best']}")

    out = {"correct": ok, "attempted": n, "failed": mismatched,
           "metrics": metrics,
           "device": {"platform": d0.platform, "kind": d0.device_kind,
                      "count": len(devs), "memory_peak_bytes": peak,
                      **device_extra}}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["compared"] = {k: {"value": nums[k], "limit": limits[k]}
                       for k in limits}
    for line in lines:
        print(line, file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
