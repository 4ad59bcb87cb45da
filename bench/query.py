"""One DSE query through the program's public entry, `run_search`, built
from a configuration and a traffic file; the task it searches for; and
its answer as plain data for the reference."""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict


def program_task(config: Dict[str, Any]):
    """The program's TaskWorkloads for a configuration's `network`: a
    paper CNN (`name`, `batch_size`, `processing`) through the task
    analyst, or an LM block (`lm`, a `repro.configs` registry id, with
    `shape` and the `overrides` the configuration cuts) lowered by
    `lower_block`, whose workloads are the task's intra-layer ones."""
    net = config["network"]
    if "lm" not in net:
        from repro.core import analyze
        from repro.core.task_analyst import NETWORKS
        return analyze(NETWORKS[net["name"]](
            batch_size=net["batch_size"], processing=net["processing"]))
    from repro.configs import ShapeSpec, get_config
    from repro.core.lower_lm import lower_block
    from repro.core.task_analyst import TaskWorkloads
    cfg = dataclasses.replace(get_config(net["lm"]),
                              **net.get("overrides", {}))
    lowered = lower_block(cfg, ShapeSpec(name=net["name"], **net["shape"]))
    return TaskWorkloads(intra=lowered.all_workloads(), preproc=[],
                         activations=[])


def make_query(config: Dict[str, Any], traffic: Dict[str, Any],
               seed: int) -> Callable[..., Any]:
    """-> query(trace) -> SearchReport.  The task is built once here
    (`program_task`); every query gets a fresh lattice and a fresh
    in-memory result cache (`cache=None`), so only compiled programs carry
    from one query to the next."""
    from repro.core import MapperConfig
    from repro.search import run_search
    from repro.search.space import ArchSpace

    task = program_task(config)
    cfg = MapperConfig(max_mappings=config["max_mappings"], seed=seed,
                       **traffic["mapper"])
    lat = config["lattice"]

    def query(trace=None):
        space = ArchSpace.spatial(num_pes=lat["num_pes"],
                                  rf_words=lat["rf_words"],
                                  gbuf_words=lat["gbuf_words"],
                                  bits=lat["bits"],
                                  zero_skip=lat["zero_skip"])
        return run_search(task, space, goal=config["goal"], cfg=cfg,
                          cache=None, trace=trace, **traffic["search"])

    return query


def answers(report) -> Dict[str, Any]:
    """The query's answer: per design the network totals and per workload
    the winning mapping with its cycles and energy; and the best design."""
    designs = {}
    for arch in report.all_archs:
        n = arch.network
        designs[arch.hardware.name] = {
            "network": {"cycles": n.cycles, "energy_pj": n.energy_pj,
                        "edp": n.edp},
            "workloads": {
                j: {"name": r.workload.name,
                    "factors": r.mapping.factors,
                    "orders": r.mapping.orders,
                    "bypass": [sorted(b) for b in r.mapping.bypass],
                    "cycles": r.estimate.cycles,
                    "energy_pj": r.estimate.energy_pj}
                for j, r in enumerate(arch.per_workload)}}
    return {"best": report.best.hardware.name, "designs": designs}
