#!/usr/bin/env python3
"""Smoke test: the DSE search on a TPU, through its normal entry points.

    python3 chip_smoke.py             # one chip: phases A, B and C
    python3 chip_smoke.py --chips 4   # four chips: the sharded path only

One chip, one process:

  A  `run_search` (exhaustive) over a 2x2 `ArchSpace.spatial` lattice for
     ResNet-18 training at batch 64 (ImageNet shapes, FW/BW/WG phases),
     no-bypass mapspaces at the default 20000-mapping budget, with
     `backend="auto"`: every row must be scored by the Pallas kernel.
  B  the same search with `backend="jnp"`, the reference: the same best
     architecture and the same per-workload winners (a float32 near-tie
     may pick another index if both winners' float64 scalar-evaluator
     scores agree within 1e-4 relative).
  C  one default-config query (bypass on, so mostly the jnp path)
     submitted to `DSEService` by three concurrent clients: they must
     coalesce onto one job whose winner equals a direct `run_search`.

With `--chips 4`, only the multi-device path runs, for ResNet-18
inference at batch 64 on two lattice points (the per-arch reference
compiles one program per architecture and workload, so it is kept
small): a no-bypass kernel search and a bypass-on search, whose kernel
and jnp groups must both shard over four distinct devices, each compared
with a `batching="per-arch"` run (which never shards) in the same
process.

Each phase prints its wall seconds, XLA compile seconds, persistent
compile-cache hits and misses, the search's jit counters, its
kernel/jnp row counts and its seconds per driver phase.  The last line of a passing run is one JSON object
naming the device; any failed check exits non-zero before it.  Without a
TPU the script names the platform it found and exits non-zero.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import threading
import time
from concurrent.futures import ThreadPoolExecutor

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

GOAL = "edp"
TIE_RTOL = 1e-4


class SmokeFailure(AssertionError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


class CompileStats:
    """XLA compile time and persistent-cache traffic, from JAX's own
    monitoring events."""

    def __init__(self):
        import jax
        self.secs = 0.0
        self.hits = 0
        self.misses = 0
        self._lock = threading.Lock()
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            with self._lock:
                self.secs += duration

    def _event(self, event, **_):
        with self._lock:
            if event == "/jax/compilation_cache/cache_hits":
                self.hits += 1
            elif event == "/jax/compilation_cache/cache_misses":
                self.misses += 1

    def snapshot(self):
        with self._lock:
            return self.secs, self.hits, self.misses


def row_counts(tracer):
    """(kernel rows, jnp rows) scored in a traced search, from the fused
    and per-arch dispatch spans."""
    kern = jnp_ = 0
    for s in tracer.buffer.snapshot():
        a = s.attrs
        if s.name == "fused.kernel-group":
            kern += a["rows"]
        elif s.name in ("fused.jnp-group", "fused.jnp-dispatch",
                        "backend.jnp"):
            jnp_ += a["rows"]
        elif s.name == "backend.pallas":
            kern += a["kernel_rows"]
            jnp_ += a["jnp_rows"]
    return kern, jnp_


def jit_counts(report):
    """(XLA compiles, fused dispatches) of a traced search."""
    jit = report.summary()["jit"]
    return jit["compile"]["n"], jit["dispatches"]


def run_phase(name, comp, fn):
    """Run one phase; print its wall and compile seconds.  A phase's
    exception propagates."""
    s0, h0, m0 = comp.snapshot()
    t0 = time.perf_counter()
    out = fn()
    wall = time.perf_counter() - t0
    s1, h1, m1 = comp.snapshot()
    print(f"[{name}] wall_s={wall!r} compile_s={s1 - s0!r} "
          f"compile_cache_hits={h1 - h0} compile_cache_misses={m1 - m0}",
          flush=True)
    return out


def describe(name, report):
    kern, jnp_ = row_counts(report.tracer)
    compiles, dispatches = jit_counts(report)
    print(f"[{name}] backend={report.backend} "
          f"archs={report.n_evaluated} best={report.best.hardware.name} "
          f"{GOAL}={report.goal_value()!r} kernel_rows={kern} "
          f"jnp_rows={jnp_} jit_compiles={compiles} "
          f"jit_dispatches={dispatches}", flush=True)
    phases = sorted(report.phase_times.items(), key=lambda kv: -kv[1])
    print(f"[{name}] phase_s={json.dumps(dict(phases))}", flush=True)
    return kern, jnp_


def compare_winners(a, b, label: str) -> int:
    """Same best architecture, and per architecture the same winning
    mapping per workload, up to float32 near-ties judged by the float64
    scalar evaluator.  -> number of near-tie swaps."""
    from repro.core.evaluator import evaluate_mapping
    from repro.core.explorer import GOALS
    score = GOALS[GOAL]
    check(a.best.hardware.name == b.best.hardware.name,
          f"{label}: best architecture {a.best.hardware.name} != "
          f"{b.best.hardware.name}")
    by_name = {r.hardware.name: r for r in b.all_archs}
    check(sorted(by_name) == sorted(r.hardware.name for r in a.all_archs),
          f"{label}: the two runs evaluated different architectures")
    ties = 0
    for ra in a.all_archs:
        rb = by_name[ra.hardware.name]
        check(len(ra.per_workload) == len(rb.per_workload),
              f"{label}: workload count differs on {ra.hardware.name}")
        for wa, wb in zip(ra.per_workload, rb.per_workload):
            ma, mb = wa.mapping, wb.mapping
            if (ma.factors, ma.orders, ma.bypass) == \
                    (mb.factors, mb.orders, mb.bypass):
                continue
            va = score(evaluate_mapping(ma))
            vb = score(evaluate_mapping(mb))
            check(math.isclose(va, vb, rel_tol=TIE_RTOL),
                  f"{label}: {ra.hardware.name}/{wa.workload.name} "
                  f"winners differ beyond a near-tie: {va!r} vs {vb!r}")
            ties += 1
    return ties


def smoke_task(processing: str = "Training"):
    from repro.core import analyze
    from repro.core.task_analyst import NETWORKS
    return analyze(NETWORKS["resnet18-im"](batch_size=64,
                                           processing=processing))


def smoke_space(num_pes=(64, 256)):
    from repro.search.space import ArchSpace
    return ArchSpace.spatial(num_pes=num_pes, rf_words=(256,),
                             gbuf_words=(64 * 1024, 128 * 1024), bits=16)


def kernel_cfg():
    from repro.core import MapperConfig
    return MapperConfig(enable_bypass=False)


def one_chip(comp, task, space, cfg_kernel, cfg_service) -> None:
    from repro.search import run_search
    from repro.serve.dse_service import DSEService, SearchQuery

    # A: the kernel path
    rep_a = run_phase("A kernel", comp, lambda: run_search(
        task, space, goal=GOAL, strategy="exhaustive", cfg=cfg_kernel,
        backend="auto", trace=True))
    kern, jnp_ = describe("A kernel", rep_a)
    check(rep_a.backend == "pallas",
          f"A: backend resolved to {rep_a.backend!r}, not pallas")
    check(kern > 0, "A: no row was scored by the kernel")
    check(jnp_ == 0, f"A: {jnp_} rows took the jnp path")

    # B: the jnp reference
    rep_b = run_phase("B reference", comp, lambda: run_search(
        task, space, goal=GOAL, strategy="exhaustive", cfg=cfg_kernel,
        backend="jnp", trace=True))
    describe("B reference", rep_b)
    ties = compare_winners(rep_a, rep_b, "A vs B")
    print(f"[B reference] winners agree: best={rep_b.best.hardware.name} "
          f"near_tie_swaps={ties}", flush=True)

    # C: the service, three clients, one job
    query = SearchQuery(task=task, space=space, goal=GOAL,
                        strategy="exhaustive", cfg=cfg_service)

    def serve():
        with DSEService(workers=2) as svc:
            with ThreadPoolExecutor(max_workers=3) as clients:
                tickets = list(clients.map(lambda _: svc.submit(query),
                                           range(3)))
            reports = [t.result(timeout=1800) for t in tickets]
            return tickets, reports, svc.snapshot()

    tickets, reports, snap = run_phase("C service", comp, serve)
    print(f"[C service] clients=3 coalesced={sum(t.coalesced for t in tickets)} "
          f"digests={len({t.digest for t in tickets})} admitted={snap['admitted']} "
          f"best={reports[0].best.hardware.name} "
          f"{GOAL}={reports[0].goal_value()!r}", flush=True)
    check(snap["admitted"] == 1 and snap["coalesced"] == 2,
          f"C: expected 1 admitted job and 2 coalesced submits, got {snap}")
    check(len({t.digest for t in tickets}) == 1, "C: digests differ")
    check(all(r is reports[0] for r in reports),
          "C: clients received different reports")
    direct = run_phase("C direct", comp, lambda: run_search(
        task, space, goal=GOAL, strategy="exhaustive", cfg=cfg_service,
        backend="auto", trace=True))
    describe("C direct", direct)
    check(compare_winners(reports[0], direct, "service vs direct") == 0,
          "C: the service and the direct search picked different mappings")
    check(reports[0].goal_value() == direct.goal_value(),
          "C: the service and the direct search disagree on the value")


def four_chips(comp, task, space, cfg_kernel, cfg_bypass) -> None:
    """Kernel and jnp groups sharded over four devices, each compared with
    the never-sharding per-arch path."""
    from repro.kernels.mapspace_eval import ops as kernel_ops
    from repro.search import batch_frontier, run_search

    devices = {"kernel": set(), "jnp": set()}
    kernel_fwd = kernel_ops.mapspace_eval_multi_fwd
    dispatch = batch_frontier._dispatch_shards

    def spy_kernel(*a, **k):
        out = kernel_fwd(*a, **k)
        devices["kernel"] |= {str(d) for d in out[0].devices()}
        return out

    def spy_dispatch(*a, **k):
        pend = dispatch(*a, **k)
        for res, _ in pend:
            devices["jnp"] |= {str(d) for d in res["valid"].devices()}
        return pend

    kernel_ops.mapspace_eval_multi_fwd = spy_kernel
    batch_frontier._dispatch_shards = spy_dispatch
    try:
        for name, cfg in (("kernel", cfg_kernel), ("jnp", cfg_bypass)):
            devices[name].clear()
            fused = run_phase(f"4chip {name} fused", comp, lambda: run_search(
                task, space, goal=GOAL, strategy="exhaustive", cfg=cfg,
                backend="auto", trace=True))
            describe(f"4chip {name} fused", fused)
            seen = sorted(devices[name])
            print(f"[4chip {name} fused] shard devices={len(seen)} {seen}",
                  flush=True)
            check(len(seen) == 4,
                  f"4 chips: {name} groups ran on {len(seen)} devices")
            per_arch = run_phase(f"4chip {name} per-arch", comp,
                                 lambda: run_search(
                                     task, space, goal=GOAL,
                                     strategy="exhaustive", cfg=cfg,
                                     backend="auto", batching="per-arch",
                                     trace=True))
            describe(f"4chip {name} per-arch", per_arch)
            ties = compare_winners(fused, per_arch, f"4 chips {name}")
            print(f"[4chip {name}] fused == per-arch: "
                  f"best={per_arch.best.hardware.name} "
                  f"near_tie_swaps={ties}", flush=True)
    finally:
        kernel_ops.mapspace_eval_multi_fwd = kernel_fwd
        batch_frontier._dispatch_shards = dispatch


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the multi-device path")
    args = ap.parse_args(argv)

    from repro.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    import jax

    devs = jax.devices()
    d0 = devs[0]
    if d0.platform != "tpu":
        print(f"chip_smoke: no TPU: JAX found platform {d0.platform!r} "
              f"({d0.device_kind}, {len(devs)} device(s))", file=sys.stderr)
        return 2
    print(f"device platform={d0.platform} kind={d0.device_kind} "
          f"count={len(devs)} compile_cache={cache_dir}", flush=True)
    check(len(devs) >= args.chips,
          f"--chips {args.chips} needs {args.chips} devices, "
          f"found {len(devs)}")
    comp = CompileStats()
    from repro.core import MapperConfig
    t0 = time.perf_counter()
    if args.chips == 4:
        four_chips(comp, smoke_task("Inference"), smoke_space((256,)),
                   kernel_cfg(), MapperConfig())
    else:
        one_chip(comp, smoke_task(), smoke_space(), kernel_cfg(),
                 MapperConfig())
    secs, hits, misses = comp.snapshot()
    print(f"[total] wall_s={time.perf_counter() - t0!r} compile_s={secs!r} "
          f"compile_cache_hits={hits} compile_cache_misses={misses}",
          flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
