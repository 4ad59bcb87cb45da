"""Cross-architecture batched mapspace evaluation.

The seed hot path dispatches one vectorized `core.batch_eval` call per
(architecture, workload) pair; a DSE round evaluating many candidate
architectures pays per-call dispatch + padding overhead dozens of times
over.  Here all pending (arch, workload) mapspaces of a round are grouped
by their structural `BatchSig` — identical level layout / tensor set, the
only things the fused evaluator needs static — and each group is packed
into a single `evaluate_batch_multi` device call with per-mapping hardware
constants.  Every architecture from one Designer template (e.g. the paper's
PEs x RF x Gbuf lattice) shares one signature, so a whole round usually
fuses into one call per workload *shape family*, not per architecture.

Jobs carry either a `core.mapspace_array.PackedMapspace` (the primary,
array-native representation — zero packing happens here) or a legacy
`Mapping` list (packed exactly once, then treated identically); group
evaluation *concatenates* the per-job arrays instead of re-packing.

Two extensions for the streaming driver (`search.driver`, overlap mode):

  * **multi-device sharding** — rows of a fused group are independent, so
    a giant group splits along the mapping axis into one contiguous shard
    per local device (`batch_eval.shard_bounds`), each padded to its own
    power-of-2 bucket, merged on the host.  Winners are bit-identical to
    the single-call path; on a one-device host the plan degenerates to
    exactly the unsharded dispatch.
  * **deferred sync** — `fused_launch` issues every jnp-group dispatch and
    returns *un-forced* device values (`@obs.deferred_sync`), so the host
    can build the next round while the device scores this one;
    `fused_collect` forces them later (the driver's "device-wait" phase).
    `fused_best` remains the synchronous form with identical winners.

Constrained searches never enqueue jobs for statically infeasible
architectures (the driver's `_Evaluator` rejects them on the hardware
description alone, before `MapspaceJob` construction), so every job that
reaches `fused_best`/`per_arch_best` — and therefore every kernel or
fused jnp call — is for a design still in the running.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..core.batch_eval import (SHARD_MIN_ROWS, bucket, evaluate_batch_multi,
                               make_static, note_batch_dispatch, pack,
                               params_of, shard_bounds, sig_of)
from ..core.designer import HardwareDesc
from ..core.mapping import Mapping
from ..core.workload import Workload
from ..obs import deferred_sync

GOAL_KEY = {"latency": "cycles", "energy": "energy_pj", "edp": "edp"}


@dataclasses.dataclass
class MapspaceJob:
    """One pending mapspace search: pick the goal-best mapping of the
    job's mapspace (all on the same hw/workload).  Provide either
    `mappings` (legacy objects) or `packed` (array-native)."""
    tag: object                       # caller identity, returned with result
    hw: HardwareDesc
    workload: Workload
    mappings: Optional[List[Mapping]] = None
    packed: Optional["object"] = None           # PackedMapspace

    def n_rows(self) -> int:
        if self.packed is not None:
            return len(self.packed)
        return len(self.mappings or [])


@dataclasses.dataclass
class JobBest:
    tag: object
    index: int                        # argmin into the job's mapspace
    value: float                      # goal score of the winner (f32 path)
    n_scored: int


@dataclasses.dataclass
class _JobArrays:
    """Packed view of one job (computed at most once per job)."""
    st: object                        # HwStatic
    factors: np.ndarray
    rank: np.ndarray
    store: np.ndarray
    eligible: np.ndarray


def _job_arrays(job: MapspaceJob, need_eligibility: bool) -> _JobArrays:
    from ..core.backend import eligibility_mask
    if job.packed is not None:
        p = job.packed
        return _JobArrays(p.static, p.factors, p.rank, p.store, p.eligible)
    st = make_static(job.hw, job.workload)
    factors, rank, store = pack(job.mappings)
    elig = (eligibility_mask(job.mappings) if need_eligibility
            else np.zeros((len(job.mappings),), bool))
    return _JobArrays(st, factors, rank, store, elig)


def _chunk(idxs: List[int], sizes: Dict[int, int],
           max_group: int) -> List[List[int]]:
    """Split a job-index group so no chunk exceeds `max_group` rows."""
    chunks: List[List[int]] = [[]]
    rows = 0
    for i in idxs:
        n = sizes[i]
        if chunks[-1] and rows + n > max_group:
            chunks.append([])
            rows = 0
        chunks[-1].append(i)
        rows += n
    return chunks


def _group_jobs(jobs: Sequence[MapspaceJob], engine: str):
    """Group job indices by BatchSig (kernel-eligible groups split out
    under the pallas engine); shared by `fused_best` and `fused_launch`
    so both produce identical group/chunk orders."""
    groups: Dict[object, List[int]] = {}
    kernel_groups: Dict[object, List[int]] = {}
    arrays: List[Optional[_JobArrays]] = [None] * len(jobs)
    sizes: Dict[int, int] = {}
    for i, job in enumerate(jobs):
        if not job.n_rows():
            raise ValueError(f"job {job.tag!r}: empty mapspace")
        a = _job_arrays(job, need_eligibility=engine == "pallas")
        arrays[i] = a
        sizes[i] = a.factors.shape[0]
        if engine == "pallas" and a.eligible.all():
            kernel_groups.setdefault(sig_of(a.st), []).append(i)
        else:
            groups.setdefault(sig_of(a.st), []).append(i)
    return groups, kernel_groups, arrays, sizes


def _eligible_rows(idxs: List[int], arrays: List[_JobArrays],
                   engine: str) -> int:
    """No-bypass rows of a jnp chunk that the pallas engine could not
    send to the kernel, because their jobs also hold bypass rows."""
    if engine != "pallas":
        return 0
    return int(sum(arrays[i].eligible.sum() for i in idxs))


def _group_arrays(idxs: List[int], arrays: List[_JobArrays]):
    """Concatenate one chunk's per-job arrays + per-row hw params."""
    counts = [arrays[i].factors.shape[0] for i in idxs]
    factors = np.concatenate([arrays[i].factors for i in idxs])
    rank = np.concatenate([arrays[i].rank for i in idxs])
    store = np.concatenate([arrays[i].store for i in idxs])
    params = {}
    per_job = [params_of(arrays[i].st, n) for i, n in zip(idxs, counts)]
    for name in per_job[0]:
        params[name] = np.concatenate([p[name] for p in per_job])
    return counts, factors, rank, store, params


def _pad_rows(factors, rank, store, params):
    """Pad the row axis to its power-of-2 bucket (repeat row 0)."""
    n = factors.shape[0]
    pad = bucket(n) - n
    if pad:
        rep = lambda a: np.concatenate([a, np.repeat(a[:1], pad, axis=0)])
        factors, rank, store = rep(factors), rep(rank), rep(store)
        params = {k: rep(v) for k, v in params.items()}
    return factors, rank, store, params


def _local_devices() -> tuple:
    from ..core.batch_eval import score_devices
    return score_devices()


def _shard_plan(n: int, devices=None) -> List[Tuple[Tuple[int, int],
                                                    object]]:
    """-> [((lo, hi), device), ...] covering [0, n).  A single entry with
    device None (no pinning — byte-identical to the unsharded dispatch)
    unless more than one device is available and the group is big enough
    that every shard clears `SHARD_MIN_ROWS`."""
    if devices is None:
        devices = _local_devices()
    if len(devices) <= 1 or n < 2 * SHARD_MIN_ROWS:
        return [((0, n), None)]
    bounds = shard_bounds(n, len(devices))
    if len(bounds) <= 1:
        return [((0, n), None)]
    return [(b, devices[i % len(devices)]) for i, b in enumerate(bounds)]


@deferred_sync
def _dispatch_shards(sig, key: str, factors, rank, store, params,
                     plan) -> List[Tuple[object, int]]:
    """Issue one `evaluate_batch_multi` dispatch per shard of `plan`
    (each padded to its own bucket, pinned to its device) and return the
    *un-forced* per-shard result dicts with their true row counts."""
    import jax.numpy as jnp

    from ..core.backend import device_scope

    pend: List[Tuple[object, int]] = []
    for (lo, hi), dev in plan:
        m = hi - lo
        f, r, s, p = _pad_rows(factors[lo:hi], rank[lo:hi], store[lo:hi],
                               {k: v[lo:hi] for k, v in params.items()})
        note_batch_dispatch(f.shape[0])
        with device_scope(dev):
            res = evaluate_batch_multi(sig, {k: jnp.asarray(v)
                                             for k, v in p.items()},
                                       jnp.asarray(f), jnp.asarray(r),
                                       jnp.asarray(s))
        pend.append((res, m))
    return pend


def _merge_shards(pend, key: str):
    """Force + concatenate per-shard results -> (scores, valid) numpy."""
    scores = np.concatenate([np.asarray(res[key][:m]) for res, m in pend])
    valid = np.concatenate([np.asarray(res["valid"][:m])
                            for res, m in pend])
    return scores, valid


def _assign_best(idxs: List[int], counts: List[int], jobs, scores,
                 out: List[Optional[JobBest]]) -> None:
    """Per-job argmin over the group's merged score vector (+inf rows
    already applied): ties break to the lowest index, seed semantics."""
    off = 0
    for i, cnt in zip(idxs, counts):
        seg = scores[off: off + cnt]
        best = int(np.argmin(seg))
        out[i] = JobBest(tag=jobs[i].tag, index=best,
                         value=float(seg[best]), n_scored=cnt)
        off += cnt


def fused_best(jobs: Sequence[MapspaceJob], goal: str = "edp",
               max_group: int = 65536,
               backend: str = "jnp") -> List[JobBest]:
    """Goal-best mapping index per job, fusing jobs across architectures.

    Jobs are grouped by BatchSig; each group evaluates as one
    `evaluate_batch_multi` call (split if it would exceed `max_group`
    rows, and sharded across local devices when a group is large enough).
    Selection semantics match `batch_eval.batch_best_index` per job:
    invalid mappings score +inf, ties break to the lowest index.

    With `backend="pallas"` (or "auto" resolving to pallas), jobs whose
    whole mapspace is kernel-eligible (no-bypass mappings — the Pallas
    kernel's storage-chain assumption) are fused per BatchSig group into
    ONE `mapspace_eval_multi` kernel call with per-row hardware
    constants; the remaining jobs keep the fused `evaluate_batch_multi`
    path, so a round that mixes bypass and no-bypass mapspaces still
    fuses everything the kernel cannot take.
    """
    from ..core.backend import resolve_backend
    engine = resolve_backend(backend)

    key = GOAL_KEY[goal]
    groups, kernel_groups, arrays, sizes = _group_jobs(jobs, engine)
    out: List[Optional[JobBest]] = [None] * len(jobs)

    from ..obs import current_tracer
    tr = current_tracer()
    for sig, idxs in kernel_groups.items():
        for chunk in _chunk(idxs, sizes, max_group):
            rows = sum(sizes[i] for i in chunk)
            with tr.span("fused.kernel-group", jobs=len(chunk),
                         rows=rows):
                _kernel_group(chunk, jobs, arrays, goal, out)

    for sig, idxs in groups.items():
        for chunk in _chunk(idxs, sizes, max_group):
            rows = sum(sizes[i] for i in chunk)
            with tr.span("fused.jnp-group", jobs=len(chunk),
                         rows=rows) as sp:
                if tr.enabled:
                    sp.set(eligible_rows=_eligible_rows(chunk, arrays,
                                                        engine))
                _eval_group(sig, chunk, jobs, arrays, key, out)
    return [b for b in out if b is not None]


# ---------------------------------------------------------------------------
# deferred launch/collect (streaming driver)
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class _PendingGroup:
    """One jnp chunk whose dispatches are in flight (un-forced)."""
    idxs: List[int]
    counts: List[int]
    pend: List[Tuple[object, int]]    # (device result dict, true rows)


@dataclasses.dataclass
class PendingFused:
    """In-flight fused round: kernel-path winners already resolved in
    `out`; jnp groups awaiting their force in `fused_collect`."""
    jobs: Sequence[MapspaceJob]
    key: str
    groups: List[_PendingGroup]
    out: List[Optional[JobBest]]


@deferred_sync
def fused_launch(jobs: Sequence[MapspaceJob], goal: str = "edp",
                 max_group: int = 65536,
                 backend: str = "jnp") -> PendingFused:
    """Issue every fused dispatch of a round and return without forcing.

    Grouping, chunking, sharding, bucket padding, and selection semantics
    are exactly `fused_best`'s — `fused_collect(fused_launch(jobs))`
    produces bit-identical winners — but the jnp groups come back as
    pending device values so the caller can overlap host work with device
    execution.  Kernel-path (pallas) groups still resolve eagerly here:
    the kernel ops force internally, which keeps their device time inside
    the launching span.
    """
    from ..core.backend import resolve_backend
    engine = resolve_backend(backend)

    key = GOAL_KEY[goal]
    groups, kernel_groups, arrays, sizes = _group_jobs(jobs, engine)
    out: List[Optional[JobBest]] = [None] * len(jobs)

    from ..obs import current_tracer
    tr = current_tracer()
    for sig, idxs in kernel_groups.items():
        for chunk in _chunk(idxs, sizes, max_group):
            rows = sum(sizes[i] for i in chunk)
            with tr.span("fused.kernel-group", jobs=len(chunk),
                         rows=rows):
                _kernel_group(chunk, jobs, arrays, goal, out)

    pending: List[_PendingGroup] = []
    for sig, idxs in groups.items():
        for chunk in _chunk(idxs, sizes, max_group):
            rows = sum(sizes[i] for i in chunk)
            with tr.span("fused.jnp-dispatch", jobs=len(chunk),
                         rows=rows) as sp:
                if tr.enabled:
                    sp.set(eligible_rows=_eligible_rows(chunk, arrays,
                                                        engine))
                counts, factors, rank, store, params = \
                    _group_arrays(chunk, arrays)
                plan = _shard_plan(factors.shape[0])
                pend = _dispatch_shards(sig, key, factors, rank, store,
                                        params, plan)
            pending.append(_PendingGroup(idxs=chunk, counts=counts,
                                         pend=pend))
    return PendingFused(jobs=jobs, key=key, groups=pending, out=out)


def fused_collect(pending: PendingFused) -> List[JobBest]:
    """Force the in-flight jnp groups of a `fused_launch` round and
    resolve per-job winners.  Callers bracket this in the span that owns
    the device time (the streaming driver's "device-wait" phase)."""
    for g in pending.groups:
        scores, valid = _merge_shards(g.pend, pending.key)
        scores = np.where(valid, scores, np.inf)
        _assign_best(g.idxs, g.counts, pending.jobs, scores, pending.out)
    return [b for b in pending.out if b is not None]


def _kernel_group(idxs: List[int], jobs, arrays: List[_JobArrays],
                  goal: str, out: List[Optional[JobBest]]) -> None:
    """Score one BatchSig group of kernel-eligible jobs with
    multi-architecture `mapspace_eval_multi` calls (interpret mode
    off-TPU), matching the +inf-invalid / low-tie selection semantics of
    the fused path.  Validity is closed-form per job (the kernel emits
    only cycles/energy).  With several local devices and a large enough
    group, whole jobs are split into per-device sub-calls (row-wise
    independent, so winners are unchanged)."""
    from ..core.backend import (default_interpret, device_scope,
                                validity_mask_arrays)
    from ..kernels.mapspace_eval import ops as _kernel_ops
    from ..obs import current_tracer

    counts = [arrays[i].factors.shape[0] for i in idxs]
    interpret = default_interpret()
    cyc_parts: List[np.ndarray] = []
    en_parts: List[np.ndarray] = []
    for sub, dev in _kernel_shard_plan(idxs, counts):
        with device_scope(dev):
            cycles, energy = _kernel_ops.mapspace_eval_multi(
                [(arrays[i].st, arrays[i].factors, arrays[i].rank)
                 for i in sub], interpret=interpret)
        cyc_parts.append(cycles)
        en_parts.append(energy)
    with current_tracer().span("kernel.select", rows=sum(counts)):
        cycles = np.concatenate(cyc_parts).astype(np.float64)
        energy = np.concatenate(en_parts).astype(np.float64)
        if goal == "latency":
            scores = cycles
        elif goal == "energy":
            scores = energy
        else:
            scores = cycles * energy
        off = 0
        for i, cnt in zip(idxs, counts):
            seg = scores[off: off + cnt].copy()
            valid = validity_mask_arrays(arrays[i].st, arrays[i].factors,
                                         arrays[i].store)
            seg[~valid] = np.inf
            best = int(np.argmin(seg))
            out[i] = JobBest(tag=jobs[i].tag, index=best,
                             value=float(seg[best]), n_scored=cnt)
            off += cnt


def _kernel_shard_plan(idxs: List[int], counts: List[int],
                       devices=None) -> List[Tuple[List[int], object]]:
    """Partition a kernel group's *jobs* (kept whole — the kernel packs
    per-job arrays) into contiguous per-device sub-lists of near-equal
    row weight.  One (all jobs, None) entry on a single-device host or
    when the group is too small to shard."""
    if devices is None:
        devices = _local_devices()
    total = sum(counts)
    if len(devices) <= 1 or len(idxs) <= 1 or total < 2 * SHARD_MIN_ROWS:
        return [(list(idxs), None)]
    n_shards = min(len(devices), len(idxs), total // SHARD_MIN_ROWS)
    if n_shards <= 1:
        return [(list(idxs), None)]
    target = total / n_shards
    plan: List[Tuple[List[int], object]] = []
    cur: List[int] = []
    acc = 0.0
    for i, cnt in zip(idxs, counts):
        cur.append(i)
        acc += cnt
        if acc >= target and len(plan) < n_shards - 1:
            plan.append((cur, devices[len(plan) % len(devices)]))
            cur, acc = [], 0.0
    if cur:
        plan.append((cur, devices[len(plan) % len(devices)]))
    return plan


def _eval_group(sig, idxs: List[int], jobs, arrays: List[_JobArrays],
                key: str, out: List[Optional[JobBest]]) -> None:
    import jax.numpy as jnp

    counts, factors, rank, store, params = _group_arrays(idxs, arrays)
    n = factors.shape[0]
    plan = _shard_plan(n)
    if len(plan) > 1:
        scores, valid = _eval_group_sharded(sig, key, factors, rank,
                                            store, params, plan)
    else:
        factors, rank, store, params = _pad_rows(factors, rank, store,
                                                 params)
        note_batch_dispatch(factors.shape[0])
        res = evaluate_batch_multi(sig, {k: jnp.asarray(v)
                                         for k, v in params.items()},
                                   jnp.asarray(factors), jnp.asarray(rank),
                                   jnp.asarray(store))
        scores = np.asarray(res[key][:n])
        valid = np.asarray(res["valid"][:n])
    scores = np.where(valid, scores, np.inf)
    _assign_best(idxs, counts, jobs, scores, out)


def _eval_group_sharded(sig, key: str, factors, rank, store, params,
                        plan):
    """Multi-device dispatch + host merge for one fused group.  Each
    shard is an independent contiguous row range, padded to its own
    bucket and pinned to its device; results are bit-identical to the
    single-call path because the evaluator is row-wise."""
    from ..obs import current_tracer
    tr = current_tracer()
    with tr.span("fused.shard-dispatch", shards=len(plan)):
        pend = _dispatch_shards(sig, key, factors, rank, store, params,
                                plan)
    with tr.span("fused.shard-merge", shards=len(pend)):
        return _merge_shards(pend, key)


def per_arch_best(jobs: Sequence[MapspaceJob], goal: str = "edp",
                  use_batch: bool = True,
                  backend: str = "jnp") -> List[JobBest]:
    """Seed-semantics fallback: one `batch_best_index` (or scalar loop)
    per job — exactly the explorer's `find_optimal_mapping` selection.
    A non-jnp `backend` swaps the batch scorer (`core.backend`) while
    keeping the per-job dispatch shape.  Packed jobs keep the same
    selection semantics (the scalar loop materializes lazily)."""
    import math as _math

    from ..core.batch_eval import batch_best_index
    from ..core.evaluator import evaluate_mapping
    from ..core.explorer import GOALS

    from ..obs import current_tracer
    tr = current_tracer()
    score = GOALS[goal]
    out: List[JobBest] = []
    for job in jobs:
        with tr.span("per-arch.job", rows=job.n_rows()):
            batch = job.packed if job.packed is not None else job.mappings
            mat = (job.packed.materialize if job.packed is not None
                   else job.mappings.__getitem__)
            if use_batch and job.n_rows() >= 64:
                best_i = batch_best_index(batch, goal, backend=backend)
                best_v = score(evaluate_mapping(mat(best_i)))
            else:
                best_v = _math.inf
                best_i = 0
                for i in range(job.n_rows()):
                    v = score(evaluate_mapping(mat(i)))
                    if v < best_v:
                        best_i, best_v = i, v
            out.append(JobBest(tag=job.tag, index=best_i, value=best_v,
                               n_scored=job.n_rows()))
    return out
