"""Beyond-paper: mapspace-evaluation throughput.

The DSE bottleneck is the whole mapspace pipeline, not just scoring.
Compares (a) the scalar Python evaluator (Timeloop-style), (b) the
vectorized jnp batch evaluator, (c) the Pallas kernel in interpret mode
(on TPU the same kernel runs on the VPU), (d) cross-architecture fused
batching (repro.search.batch_frontier): the mapspaces of several
candidate architectures packed into one device call instead of one call
per arch, and (e) the array-native front-end
(`core.mapspace_array.build_packed_mapspace`) against the legacy object
constructor — construction + validation + packing, the part of DSE time
the evaluator PRs never touched.  Reported as microseconds per mapping."""
from __future__ import annotations

import time

import numpy as np

from repro.core import (MapperConfig, alexnet_cifar, analyze,
                        build_mapspace, build_packed_mapspace,
                        evaluate_mapping, make_spatial_arch)
from repro.core.batch_eval import evaluate_batch, make_static, pack

from .common import Timer, claim


def run(n=2000, max_mappings=20000):
    hw = make_spatial_arch(num_pes=256, rf_words=256, gbuf_words=64 * 1024,
                           bits=16, zero_skip=True)
    wl = analyze(alexnet_cifar(batch_size=16)).intra[2]
    cfg = MapperConfig(max_mappings=3 * n, seed=0, enable_bypass=False)
    ms = build_mapspace(wl, hw, cfg).mappings[:n]
    n = len(ms)

    t0 = time.time()
    for m in ms[:200]:
        evaluate_mapping(m)
    scalar_us = (time.time() - t0) * 1e6 / 200

    st = make_static(hw, wl)
    f, r, s = pack(ms)
    evaluate_batch(st, f, r, s)          # compile
    t0 = time.time()
    out = evaluate_batch(st, f, r, s)
    _ = np.asarray(out["cycles"])
    batch_us = (time.time() - t0) * 1e6 / n

    from repro.core.backend import default_interpret
    from repro.kernels.mapspace_eval.ops import mapspace_eval
    t0 = time.time()
    mapspace_eval(ms, block=256, interpret=default_interpret())
    kernel_us = (time.time() - t0) * 1e6 / n

    # backend dispatch layer over the same mapspace (jnp vs routed pallas;
    # the dedicated jnp-vs-pallas throughput claim lives in
    # bench_backend_dispatch).  Overhead baseline is batch_scores — the
    # engine the jnp backend wraps, packing included — not the pre-packed
    # evaluate_batch call above.
    from repro.core.backend import score_mapspace
    from repro.core.batch_eval import batch_scores
    score_mapspace(ms, "edp", "jnp")                 # compile
    engine_us = min(_timed(lambda: batch_scores(ms, "edp"))
                    for _ in range(3)) * 1e6 / n
    disp_jnp_us = min(_timed(lambda: score_mapspace(ms, "edp", "jnp"))
                      for _ in range(3)) * 1e6 / n
    disp_pal_us = min(_timed(lambda: score_mapspace(ms, "edp", "pallas"))
                      for _ in range(3)) * 1e6 / n

    # (d) cross-arch fused batching vs one vectorized call per arch.
    # Same workload, four architectures from the Designer lattice; the seed
    # path packs + evaluates each arch separately, the fused path packs all
    # four mapspaces into one evaluate_batch_multi call.
    from repro.search.batch_frontier import MapspaceJob, fused_best
    archs = [make_spatial_arch(num_pes=p, rf_words=r, gbuf_words=g,
                               bits=16, zero_skip=True)
             for p, r, g in ((256, 256, 64 * 1024), (256, 128, 128 * 1024),
                             (512, 256, 64 * 1024), (512, 512, 128 * 1024))]
    jobs = [MapspaceJob(tag=i, hw=a, workload=wl,
                        mappings=build_mapspace(wl, a, cfg).mappings[:n])
            for i, a in enumerate(archs)]
    total = sum(len(j.mappings) for j in jobs)

    def single_arch_pass():
        for j in jobs:
            st_j = make_static(j.hw, j.workload)
            f_j, r_j, s_j = pack(j.mappings)
            np.asarray(evaluate_batch(st_j, f_j, r_j, s_j)["edp"])

    single_arch_pass()                   # compile all variants
    fused_best(jobs, "edp")              # compile the fused variant
    single_us = min(_timed(single_arch_pass) for _ in range(5)) * 1e6 / total
    fused_us = min(_timed(lambda: fused_best(jobs, "edp"))
                   for _ in range(5)) * 1e6 / total

    # (e) front-end: packed (array-native) vs object construction at the
    # full sampling budget.  The object path's product is a Mapping list
    # that every scorer must still pack(), so packing is part of its
    # cost; the packed path's arrays are the scoring input as-is.
    from repro.core.backend import score_mapspace
    cfg_b = MapperConfig(max_mappings=max_mappings, seed=0,
                         enable_bypass=True)
    obj_s = min(_timed(lambda: build_mapspace(wl, hw, cfg_b))
                for _ in range(2))
    ms_obj = build_mapspace(wl, hw, cfg_b).mappings
    pack_s = min(_timed(lambda: pack(ms_obj)) for _ in range(2))
    pkd_s = min(_timed(lambda: build_packed_mapspace(wl, hw, cfg_b))
                for _ in range(2))
    pm = build_packed_mapspace(wl, hw, cfg_b)
    nb = len(pm)
    build_speedup = (obj_s + pack_s) / pkd_s
    # construction-vs-scoring split of the packed pipeline: where does a
    # fresh (arch, workload) evaluation spend its time now?
    score_mapspace(pm, "edp", "jnp")                 # compile
    pscore_s = min(_timed(lambda: score_mapspace(pm, "edp", "jnp"))
                   for _ in range(3))

    res = {"n": n, "scalar_us": scalar_us, "batch_us": batch_us,
           "kernel_interpret_us": kernel_us,
           "speedup_batch": scalar_us / batch_us,
           "engine_jnp_us": engine_us,
           "backend_jnp_us": disp_jnp_us,
           "backend_pallas_us": disp_pal_us,
           "cross_arch_n": total, "single_arch_us": single_us,
           "fused_us": fused_us, "fused_speedup": single_us / fused_us,
           "build_max_mappings": max_mappings, "build_n_survivors": nb,
           "build_object_us": (obj_s + pack_s) * 1e6 / nb,
           "build_packed_us": pkd_s * 1e6 / nb,
           "build_speedup": build_speedup,
           "packed_score_us": pscore_s * 1e6 / nb,
           "packed_front_end_frac": pkd_s / (pkd_s + pscore_s)}
    claim(res, "backend dispatch overhead over batch_scores <= 25%",
          disp_jnp_us <= engine_us * 1.25,
          f"engine={engine_us:.2f}us dispatch={disp_jnp_us:.2f}us "
          f"per mapping")
    claim(res, "vectorized evaluator beats scalar by >10x",
          res["speedup_batch"] > 10,
          f"{scalar_us:.1f}us -> {batch_us:.2f}us per mapping "
          f"({res['speedup_batch']:.0f}x)")
    # timing-noise tolerance: the two passes sit ~1us apart per
    # mapping, and repeated A/B runs swing +-15% either way on shared
    # hardware at fast-mode batch sizes (2400 fused rows); the claim
    # guards against a real fusion regression, not scheduler jitter, so
    # the fast bar is wide and the full-budget bar (8000 rows, where
    # fusion separates cleanly) stays tight
    fuse_bar = 1.10 if max_mappings >= 5000 else 1.25
    claim(res, f"cross-arch fused batching throughput >= single-arch "
          f"path ({(fuse_bar - 1) * 100:.0f}% timing-noise tolerance)",
          fused_us <= single_us * fuse_bar,
          f"{single_us:.2f}us -> {fused_us:.2f}us per mapping "
          f"({res['fused_speedup']:.2f}x, {len(jobs)} archs fused)")
    # fast budgets leave only a few hundred survivors, so the race is
    # partly measurement-overhead-dominated and the margin narrows (PR 3
    # measured 5.1x fast vs ~10x full); the full-budget bar stays at 5x
    build_bar = 5.0 if max_mappings >= 5000 else 3.5
    claim(res, f"packed_build: array-native construction+validation >= "
          f"{build_bar:g}x the object path",
          build_speedup >= build_bar,
          f"{res['build_object_us']:.1f}us -> {res['build_packed_us']:.1f}"
          f"us per mapping ({build_speedup:.1f}x at "
          f"max_mappings={max_mappings}, {nb} survivors)")
    return res


def _timed(fn):
    t0 = time.time()
    fn()
    return time.time() - t0


def rows(res):
    return [
        ("mapspace_scalar", res["scalar_us"], "per-mapping"),
        ("mapspace_batch_jnp", res["batch_us"],
         f"speedup={res['speedup_batch']:.0f}x"),
        ("mapspace_pallas_interpret", res["kernel_interpret_us"],
         "interpret-mode (correctness path)"),
        ("mapspace_backend_jnp", res["backend_jnp_us"],
         "score_mapspace dispatch, jnp engine"),
        ("mapspace_backend_pallas", res["backend_pallas_us"],
         "score_mapspace dispatch, pallas engine (interpret off-TPU)"),
        ("mapspace_single_arch", res["single_arch_us"],
         f"4-arch loop, n={res['cross_arch_n']}"),
        ("mapspace_cross_arch_fused", res["fused_us"],
         f"speedup={res['fused_speedup']:.2f}x vs single-arch"),
        ("mapspace_build_object", res["build_object_us"],
         f"legacy constructor+validator+pack, "
         f"max_mappings={res['build_max_mappings']}"),
        ("mapspace_build_packed", res["build_packed_us"],
         f"speedup={res['build_speedup']:.1f}x vs object front-end"),
        ("mapspace_packed_score", res["packed_score_us"],
         f"front-end is {res['packed_front_end_frac']:.0%} of "
         f"build+score on the packed pipeline"),
    ]
