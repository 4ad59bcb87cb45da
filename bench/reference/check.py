"""The comparison that decides a run's `correct`.

The program's answer to a DSE query is, for every design of the lattice
and every workload of the network, a winning mapping with its cycles and
energy; per design the network's cycles, energy and EDP; and the best
design.  The plain reference (`trim/`, float64, imports nothing of the
program; an LM network's workloads from its own file in `networks/`)
judges it:

  missing        designs or workloads with no answer, or under another name
  invalid        winners the reference's validator refuses, or that
                 bypass a buffer in a query that allows no bypass
  estimate_gap   the widest relative gap between a reported cycles,
                 energy or EDP (per workload, and per design for the
                 network) and the reference's evaluation of the same
                 mapping
  regret         how far the served choice lies above the reference's
                 best, relative: for every (design, workload) the
                 reference scores every candidate of the mapspace that the
                 seed draws, and the served winner is compared with their
                 best; at the top, the served best design against the
                 best of the reference's network totals

Workloads of one shape share a mapspace, which is scored once.  Where a
query has many mapspaces they are scored by a pool of worker processes,
started afresh (not forked, so that none inherits the device runtime's
threads), which import nothing but the reference.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import math
import multiprocessing
import os
from concurrent.futures import ProcessPoolExecutor
from pathlib import Path
from typing import Any, Dict, List, Tuple

from .lowp import number_type
from .trim.designer import make_spatial_arch
from .trim.evaluator import evaluate_mapping, evaluate_network
from .trim.mapper import MapperConfig, build_mapspace, validate
from .trim.mapping import Mapping
from .trim.task_analyst import NETWORKS, analyze
from .trim.workload import TENSORS

CACHE_LEVEL = "Gbuf"
LIMITS = Path(__file__).resolve().parent / "limits.json"
MAX_WORKERS = 8
SPACES_PER_WORKER = 24     # below this many mapspaces a worker costs more


def load_limits() -> Dict[str, float]:
    """Each compared number's limit (`limits.json`; PERF.md gives the
    readings each was set from)."""
    return {k: v for k, v in json.loads(LIMITS.read_text()).items()
            if not k.startswith("_")}


def _rel(p: float, r: float) -> float:
    return abs(p - r) / max(abs(r), 1e-300)


def reference_task(config: Dict[str, Any]):
    """The reference's TaskWorkloads for a configuration's `network`: a
    paper CNN from the frozen task analyst; an LM network from `task
    (config)` of its reference network file, at the `reference_file` that
    `bench/cell.py` found under the cell's root."""
    net = config["network"]
    if "lm" not in net:
        return analyze(NETWORKS[net["name"]](batch_size=net["batch_size"],
                                              processing=net["processing"]))
    spec = importlib.util.spec_from_file_location(
        "bench_reference_network", net["reference_file"])
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.task(config)


def reference_designs(config: Dict[str, Any]) -> list:
    """The lattice, row-major (first axis outermost), named as the
    program names its designs."""
    lat = config["lattice"]
    out = []
    for pe in lat["num_pes"]:
        for rf in lat["rf_words"]:
            for gb in lat["gbuf_words"]:
                out.append(make_spatial_arch(
                    name=f"pe{pe}_rf{rf}_gb{gb}", num_pes=pe, rf_words=rf,
                    gbuf_words=gb, bits=lat["bits"],
                    zero_skip=lat["zero_skip"]))
    return out


def mapper_config(config: Dict[str, Any], traffic: Dict[str, Any],
                  seed: int) -> MapperConfig:
    return MapperConfig(max_mappings=config["max_mappings"], seed=seed,
                        **traffic["mapper"])


def _mapping(wl, hw, ans: Dict[str, Any]) -> Mapping:
    return Mapping(workload=wl, hardware=hw,
                   factors=tuple(tuple(int(x) for x in f)
                                 for f in ans["factors"]),
                   orders=tuple(None if o is None else tuple(o)
                                for o in ans["orders"]),
                   bypass=tuple(frozenset(b) for b in ans["bypass"]))


def _edp(est) -> float:
    return float(est.cycles) * float(est.energy_pj)


def _shape(wl):
    """What a workload's mapspace and scores depend on: all but its
    names."""
    return dataclasses.replace(wl, name="", layer="")


_WORKER: Dict[str, "Reference"] = {}


def _init_worker(config, traffic, seed) -> None:
    _WORKER["ref"] = Reference(config, traffic, seed)


def _score_job(job):
    d, j, ranks = job
    return _WORKER["ref"].score_space(d, j, ranks)


class Reference:
    """The reference for one (configuration, traffic, seed)."""

    def __init__(self, config: Dict[str, Any], traffic: Dict[str, Any],
                 seed: int):
        self.config, self.traffic, self.seed = config, traffic, seed
        self.task = reference_task(config)
        self.designs = reference_designs(config)
        self.cfg = mapper_config(config, traffic, seed)
        # (design, workload) -> the first pair of its mapspace
        first: Dict[Tuple[int, Any], Tuple[int, int]] = {}
        self.space = {(d, j): first.setdefault((d, _shape(wl)), (d, j))
                      for d in range(len(self.designs))
                      for j, wl in enumerate(self.task.intra)}
        self._best: Dict[Tuple[int, int], float] = {}
        self._ranked: Dict[str, Dict[Tuple[int, int], Dict]] = {}

    def score_space(self, d: int, j: int, ranks=()) -> Tuple[float, Dict]:
        """Every candidate of the mapspace of pair (d, j) that the seed
        draws, scored: -> (the least float64 EDP, per precision of
        `ranks` the candidate that ranks first by its EDP computed in
        that precision, as plain data)."""
        cands = build_mapspace(self.task.intra[j], self.designs[d],
                               self.cfg).mappings
        best = min(_edp(evaluate_mapping(m)) for m in cands)
        firsts = {}
        for p in ranks:
            F = number_type(p)
            top, top_m = None, None
            for m in cands:
                est = evaluate_mapping(m, F=F)
                score = est.cycles * est.energy_pj
                if top is None or score < top:
                    top, top_m = score, m
            firsts[p] = {"factors": top_m.factors, "orders": top_m.orders,
                         "bypass": [sorted(b) for b in top_m.bypass]}
        return best, firsts

    def score_spaces(self, ranks=()) -> None:
        """Score every mapspace of the query once, in a pool of worker
        processes where there are many."""
        ranks = tuple(p for p in ranks if p not in self._ranked)
        todo = sorted({s for s in self.space.values()
                       if s not in self._best or ranks})
        if not todo:
            return
        jobs = [(d, j, ranks) for d, j in todo]
        n = min(MAX_WORKERS, os.cpu_count() or 1,
                len(jobs) // SPACES_PER_WORKER)
        if n <= 1:
            results = [self.score_space(*job) for job in jobs]
        else:
            with ProcessPoolExecutor(
                    n, mp_context=multiprocessing.get_context("spawn"),
                    initializer=_init_worker,
                    initargs=(self.config, self.traffic, self.seed)) as ex:
                results = list(ex.map(_score_job, jobs))
        for p in ranks:
            self._ranked[p] = {}
        for s, (best, firsts) in zip(todo, results):
            self._best[s] = best
            for p, w in firsts.items():
                self._ranked[p][s] = w

    def best_edp(self, d: int, j: int) -> float:
        self.score_spaces()
        return self._best[self.space[(d, j)]]

    def network(self, hw, ests) -> Any:
        """The reference's network totals over the per-workload
        (mapping, estimate) pairs of one design."""
        max_buf = max(sum(m.buffer_words(li, t) for t in TENSORS)
                      for m, _ in ests
                      for li in hw.memory_level_indices()
                      if hw.tiling_levels[li].name == CACHE_LEVEL)
        return evaluate_network(hw, [e for _, e in ests],
                                self.task.preproc, self.task.activations,
                                cache_level=CACHE_LEVEL,
                                mapping_buffer_words=max_buf)

    def compare(self, answers: Dict[str, Any]) -> Dict[str, float]:
        """`answers` = {"best": name, "designs": {name: {"network":
        {cycles, energy_pj, edp}, "workloads": {j: {name, factors, orders,
        bypass, cycles, energy_pj}}}}}."""
        self.score_spaces()
        nums = {"missing": 0, "invalid": 0, "estimate_gap": 0.0,
                "regret": 0.0}
        intra = self.task.intra
        allow_bypass = self.cfg.enable_bypass
        network_edp: Dict[str, float] = {}
        for d, hw in enumerate(self.designs):
            a = answers["designs"].get(hw.name)
            if a is None:
                nums["missing"] += 1
                continue
            ests = []
            for j, wl in enumerate(intra):
                w = a["workloads"].get(j)
                if w is None or w["name"] != wl.name:
                    nums["missing"] += 1
                    continue
                try:
                    m = _mapping(wl, hw, w)
                except AssertionError:
                    nums["invalid"] += 1
                    continue
                if not validate(m, self.cfg.act_reserve) or (
                        not allow_bypass and any(m.bypass)):
                    nums["invalid"] += 1
                est = evaluate_mapping(m)
                for key, ref in (("cycles", est.cycles),
                                 ("energy_pj", est.energy_pj)):
                    nums["estimate_gap"] = max(nums["estimate_gap"],
                                               _rel(w[key], ref))
                nums["estimate_gap"] = max(
                    nums["estimate_gap"],
                    _rel(float(w["cycles"]) * float(w["energy_pj"]),
                         _edp(est)))
                best = self.best_edp(d, j)
                nums["regret"] = max(nums["regret"],
                                     (_edp(est) - best) / best)
                ests.append((m, est))
            if len(ests) != len(intra):
                continue
            net = self.network(hw, ests)
            network_edp[hw.name] = net.edp
            for key, ref in (("cycles", net.cycles),
                             ("energy_pj", net.energy_pj),
                             ("edp", net.edp)):
                nums["estimate_gap"] = max(nums["estimate_gap"],
                                           _rel(a["network"][key], ref))
        if len(network_edp) == len(self.designs) \
                and answers.get("best") in network_edp:
            best = min(network_edp.values())
            nums["regret"] = max(
                nums["regret"], (network_edp[answers["best"]] - best) / best)
        else:
            nums["missing"] += 1
        return nums

    def control_answers(self, rank: str, report: str = "float64"
                        ) -> Dict[str, Any]:
        """The reference in the program's place: it ranks every mapspace
        by EDP computed in `rank` and reports its winners' cycles and
        energy, and the network totals, computed in `report`."""
        self.score_spaces(ranks=(rank,))
        F = number_type(report)
        designs: Dict[str, Any] = {}
        totals = {}
        for d, hw in enumerate(self.designs):
            ws, ests = {}, []
            for j, wl in enumerate(self.task.intra):
                w = dict(self._ranked[rank][self.space[(d, j)]],
                         name=wl.name)
                m = _mapping(wl, hw, w)
                est = evaluate_mapping(m, F=F)
                ws[j] = dict(w, cycles=float(est.cycles),
                             energy_pj=float(est.energy_pj))
                ests.append((m, est))
            net = self.network(hw, ests)
            designs[hw.name] = {
                "network": {"cycles": float(net.cycles),
                            "energy_pj": float(net.energy_pj),
                            "edp": float(net.edp)},
                "workloads": ws}
            totals[hw.name] = net.edp
        return {"best": min(totals, key=lambda k: totals[k]),
                "designs": designs}


def judge(nums: Dict[str, float], limits: Dict[str, float]
          ) -> Tuple[bool, List[str]]:
    """-> (correct, one line per number: name, value and limit)."""
    lines, ok = [], True
    for name, limit in limits.items():
        v = nums[name]
        good = math.isfinite(v) and v <= limit
        ok &= good
        lines.append(f"{name}={v!r} limit={limit!r}"
                     f"{'' if good else ' FAILED'}")
    return ok, lines
