"""TRIM Explorer (paper §6.3, Algorithm 1).

For each hardware description in the architecture space:
  for each intra-layer workload: build + evaluate its mapspace, keep the
  optimal mapping per the design goal; then combine optimal mappings with
  inter-layer workloads into a network-level estimate; finally select the
  optimal architecture.

Identical workloads (repeated layers) share one mapspace evaluation.
Evaluation uses the vectorized batch evaluator for mapspaces of 64 or more
mappings (`use_batch=False` selects the scalar path explicitly); a batch
scorer error propagates.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Callable, Dict, Iterable, List, Optional, Sequence, Tuple

from .designer import HardwareDesc
from .evaluator import (Estimate, NetworkEstimate, evaluate_mapping,
                        evaluate_network)
from .mapper import MapperConfig, Mapspace, build_mapspace
from .mapping import Mapping
from .task_analyst import TaskDescription, TaskWorkloads, analyze
from .workload import TENSORS, Workload

GOALS: Dict[str, Callable[[Estimate], float]] = {
    "latency": lambda e: e.cycles,
    "energy": lambda e: e.energy_pj,
    "edp": lambda e: e.edp,
}


@dataclasses.dataclass
class WorkloadResult:
    workload: Workload
    mapping: Mapping
    estimate: Estimate
    mapspace_size: int
    n_valid: int


@dataclasses.dataclass
class ArchResult:
    hardware: HardwareDesc
    network: NetworkEstimate
    per_workload: List[WorkloadResult]

    def goal_value(self, goal: str) -> float:
        if goal == "latency":
            return self.network.cycles
        if goal == "energy":
            return self.network.energy_pj
        return self.network.edp


@dataclasses.dataclass
class ExplorationResult:
    best: ArchResult
    all_archs: List[ArchResult]
    goal: str


def _workload_key(wl: Workload):
    return (wl.dims, wl.stride, wl.dilation, wl.kind, wl.depthwise,
            round(wl.input_zero_frac, 9), round(wl.weight_zero_frac, 9))


def _best_of_extras(extra_candidates, workload, cfg, score, best_m,
                    best_e, best_v):
    """Race caller-supplied candidate mappings against the mapspace
    winner (same goal, same evaluator); the better mapping wins.
    Candidates go through the mapper's §5 resource validator first —
    `evaluate_mapping` scores invalid mappings optimistically, so an
    unchecked warm-start could otherwise win with an infeasible tile."""
    from .mapper import validate
    for cand in (extra_candidates(workload) if extra_candidates else ()):
        if not validate(cand, cfg.act_reserve):
            continue
        e = evaluate_mapping(cand)
        v = score(e)
        if v < best_v:
            best_m, best_e, best_v = cand, e, v
    return best_m, best_e, best_v


def find_optimal_mapping(workload: Workload, hw: HardwareDesc,
                         cfg: Optional[MapperConfig] = None,
                         goal: str = "edp",
                         use_batch: bool = True,
                         backend: str = "jnp",
                         use_packed: bool = False,
                         extra_candidates: Optional[
                             Callable[[Workload], Sequence[Mapping]]]
                         = None) -> WorkloadResult:
    """Search one workload's mapspace for the goal-optimal mapping.

    `backend` selects the batch scoring engine (`core.backend`): the seed
    default "jnp", "pallas" for the mapspace-eval kernel (no-bypass rows),
    or "auto" (pallas iff a TPU is attached).

    `use_packed=True` takes the array-native pipeline
    (`core.mapspace_array`): vectorized construction/validation, batch
    scoring over the packed arrays, and winner-only `Mapping`
    materialization.  The default keeps the seed object path (bit-exact,
    including the scalar-loop selection for tiny mapspaces).

    `extra_candidates(workload)` may supply additional `Mapping`s (e.g.
    a warm-start carried over from a related search) that are evaluated
    against the mapspace winner; the best of all candidates is
    returned."""
    cfg = cfg or MapperConfig()
    score = GOALS[goal]
    if use_packed:
        from .batch_eval import batch_best_index
        from .mapspace_array import build_packed_mapspace
        pm = build_packed_mapspace(workload, hw, cfg)
        if not len(pm):
            raise RuntimeError(
                f"empty valid mapspace for {workload.name} on {hw.name}")
        idx = batch_best_index(pm, goal, backend=backend)
        best_m = pm.materialize(idx)
        best_e = evaluate_mapping(best_m)
        best_m, best_e, _ = _best_of_extras(extra_candidates, workload,
                                            cfg, score, best_m, best_e,
                                            score(best_e))
        return WorkloadResult(workload=workload, mapping=best_m,
                              estimate=best_e,
                              mapspace_size=pm.total_candidates,
                              n_valid=pm.n_valid)
    space = build_mapspace(workload, hw, cfg)
    if not space.mappings:
        raise RuntimeError(
            f"empty valid mapspace for {workload.name} on {hw.name}")
    best_m, best_e, best_v = None, None, math.inf
    if use_batch and len(space.mappings) >= 64:
        from .batch_eval import batch_best_index
        idx = batch_best_index(space.mappings, goal, backend=backend)
        best_m = space.mappings[idx]
        best_e = evaluate_mapping(best_m)
        best_v = score(best_e)
    else:
        for m in space.mappings:
            e = evaluate_mapping(m)
            v = score(e)
            if v < best_v:
                best_m, best_e, best_v = m, e, v
    best_m, best_e, best_v = _best_of_extras(extra_candidates, workload,
                                             cfg, score, best_m, best_e,
                                             best_v)
    return WorkloadResult(workload=workload, mapping=best_m, estimate=best_e,
                          mapspace_size=space.total_candidates,
                          n_valid=space.n_valid)


def evaluate_architecture(task_workloads: TaskWorkloads, hw: HardwareDesc,
                          cfg: Optional[MapperConfig] = None,
                          goal: str = "edp",
                          cache_level: str = "Gbuf",
                          use_batch: bool = True,
                          backend: str = "jnp",
                          use_packed: bool = False,
                          extra_candidates: Optional[
                              Callable[[Workload], Sequence[Mapping]]]
                          = None) -> ArchResult:
    """Algorithm 1 lines 6-15 for one hardware description."""
    cfg = cfg or MapperConfig()
    cache: Dict[tuple, WorkloadResult] = {}
    results: List[WorkloadResult] = []
    for wl in task_workloads.intra:
        key = _workload_key(wl)
        if key not in cache:
            cache[key] = find_optimal_mapping(
                wl, hw, cfg, goal, use_batch, backend=backend,
                use_packed=use_packed, extra_candidates=extra_candidates)
        r = cache[key]
        results.append(dataclasses.replace(r, workload=wl))
    max_buf = 0.0
    for r in results:
        for li in hw.memory_level_indices():
            lv = hw.tiling_levels[li]
            if lv.name == cache_level:
                used = sum(r.mapping.buffer_words(li, t) for t in TENSORS)
                max_buf = max(max_buf, used)
    network = evaluate_network(
        hw, [r.estimate for r in results], task_workloads.preproc,
        task_workloads.activations, cache_level=cache_level,
        mapping_buffer_words=max_buf)
    return ArchResult(hardware=hw, network=network, per_workload=results)


def explore(task: TaskDescription, arch_space: Iterable[HardwareDesc],
            goal: str = "edp", cfg: Optional[MapperConfig] = None,
            cache_level: str = "Gbuf", use_batch: bool = True,
            verbose: bool = False,
            backend: str = "jnp") -> ExplorationResult:
    """Paper Algorithm 1 — full design-space exploration.

    Thin compatibility wrapper over `repro.search.run_search` with the
    exhaustive strategy and the seed per-(arch, workload) evaluation path;
    `repro.search` adds budgeted strategies (random/anneal/evolve),
    Pareto-frontier objectives, cross-architecture batching and a
    persistent result cache on the same machinery.  `backend` keeps the
    seed's jnp scoring by default (bit-exact parity); "pallas"/"auto"
    route scoring through `core.backend.score_mapspace`.
    """
    from ..search.driver import run_search
    report = run_search(task, list(arch_space), goal=goal, cfg=cfg,
                        cache_level=cache_level, use_batch=use_batch,
                        strategy="exhaustive", batching="per-arch",
                        backend=backend, verbose=verbose)
    return ExplorationResult(best=report.best, all_archs=report.all_archs,
                             goal=goal)
