"""Benchmark harness — one module per paper table/figure (+ beyond-paper).

Prints ``name,us_per_call,derived`` CSV; exits non-zero if any paper claim
fails.  ``--fast`` shrinks mapspace budgets for CI.

Besides the per-run ``--json-out`` dump, every run rewrites a stable
top-level ``BENCH_results.json`` (module -> {rows: {name: us_per_call},
claims}) so the perf trajectory is machine-diffable across PRs:
``git diff BENCH_results.json`` answers "what got faster/slower".
"""
from __future__ import annotations

import argparse
import importlib
import json
import os
import sys
import traceback

MODULES = [
    ("table4_fpga_resources", {}),
    ("fig08_09_fpga_validation", {"max_mappings": 4000}),
    ("fig10_12_fpga_scaling", {"max_mappings": 4000}),
    ("fig15_eyeriss", {"max_mappings": 6000}),
    ("fig16_17_zero_skipping", {"max_mappings": 3000}),
    ("fig18_19_batch_size", {"max_mappings": 3000}),
    ("fig20_21_edp_dse", {"max_mappings": 1500}),
    ("bench_search_strategies", {"max_mappings": 800}),
    ("bench_mix_search", {"max_mappings": 1200}),
    ("bench_pipeline_overlap", {"max_mappings": 2000}),
    ("bench_trim_planner", {}),
    ("bench_obs", {"max_mappings": 1500}),
    ("bench_analysis", {}),
]

FAST_OVERRIDES = {"max_mappings": 600}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true")
    ap.add_argument("--only", default=None)
    ap.add_argument("--json-out", default="experiments/benchmarks.json")
    ap.add_argument("--trace", default=None, metavar="OUT.json",
                    help="export a Chrome trace (chrome://tracing / "
                         "Perfetto) of the whole harness run")
    args = ap.parse_args()

    # Ambient tracer: every module's pipeline phases (pack/validate/score/
    # cache) record here, so each BENCH row carries its phase-time
    # breakdown; --trace additionally exports the full span tree.
    from repro.obs import Tracer, activate
    tracer = Tracer()

    all_rows = []
    all_claims = []
    results = {}
    bench_summary = {}
    failed = False
    for name, kw in MODULES:
        if args.only and args.only not in name:
            continue
        if args.fast:
            kw = {k: (FAST_OVERRIDES.get(k, v)) for k, v in kw.items()}
        mod = importlib.import_module(f"benchmarks.{name}")
        print(f"== {name} ==", flush=True)
        phases_before = tracer.phase_times()
        try:
            with activate(tracer), tracer.span(f"bench.{name}"):
                res = mod.run(**kw)
        except Exception:
            traceback.print_exc()
            failed = True
            continue
        results[name] = res
        all_claims += res.get("claims", [])
        import jax
        jax.clear_caches()          # bound the XLA code-cache footprint
        mod_rows = mod.rows(res)
        all_rows += mod_rows
        phases_after = tracer.phase_times()
        phase_delta = {
            k: round(v - phases_before.get(k, 0.0), 3)
            for k, v in phases_after.items()
            if v - phases_before.get(k, 0.0) > 0.0005}
        bench_summary[name] = {
            # budget mode matters for cross-PR diffs: a --fast run must
            # never silently overwrite full-budget numbers unnoticed
            "mode": "fast" if args.fast else "full",
            "rows": {r: round(us, 2) for r, us, _ in mod_rows},
            "claims": res.get("claims", []),
            # seconds spent per pipeline phase while this module ran
            "phase_times": phase_delta,
        }

    print("\nname,us_per_call,derived")
    for name, us, derived in all_rows:
        print(f"{name},{us:.1f},{derived}")

    n_ok = sum(1 for c in all_claims if c["ok"])
    print(f"\npaper-claims: {n_ok}/{len(all_claims)} pass")
    for c in all_claims:
        if not c["ok"]:
            print(f"  FAILED: {c['claim']} — {c['detail']}")
            failed = True

    if args.trace:
        os.makedirs(os.path.dirname(os.path.abspath(args.trace)),
                    exist_ok=True)
        tracer.export_chrome(args.trace)
        print(f"trace: {args.trace} ({len(tracer.buffer)} spans)")

    if args.json_out:
        os.makedirs(os.path.dirname(args.json_out), exist_ok=True)
        with open(args.json_out, "w") as f:
            json.dump({"claims": all_claims,
                       "rows": [list(r) for r in all_rows]}, f, indent=1,
                      default=str)
    # stable top-level snapshot: PR-over-PR perf trajectory, diffable.
    # Partial runs (--only/--fast failures) merge into the existing file
    # so one filtered run never drops the other modules' numbers.
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    bench_path = os.path.join(root, "BENCH_results.json")
    merged = {}
    if os.path.exists(bench_path):
        try:
            with open(bench_path) as f:
                merged = json.load(f)
        except json.JSONDecodeError:
            merged = {}
    merged.update(bench_summary)
    with open(bench_path, "w") as f:
        json.dump(merged, f, indent=1, sort_keys=True, default=str)
        f.write("\n")
    sys.exit(1 if failed else 0)


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
