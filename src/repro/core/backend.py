"""Backend dispatch for mapspace scoring: one entry point, two engines.

`score_mapspace` scores a batch of mappings (all on one hardware/workload
pair) and routes each mapping to one of two numerically-matched engines:

  * ``jnp``    — `core.batch_eval.evaluate_batch_multi` on one group, the
    vectorized oracle (validated against the scalar evaluator and the loop
    simulator);
  * ``pallas`` — `kernels.mapspace_eval.mapspace_eval_multi` on one group,
    the paper's mapping-scoring hot loop as a Pallas TPU kernel (VPU
    vector arithmetic with the mapping axis on the lanes).  On hosts
    without a TPU the kernel runs under
    ``pl.pallas_call(..., interpret=True)`` so the code path is always
    testable; on TPU it compiles for the VPU.

Batches are array-native end to end: a `core.mapspace_array.PackedMapspace`
is consumed without any conversion, and a legacy `Sequence[Mapping]` is
packed exactly once here — the packed arrays are shared by the kernel
scorer, the jnp fallback, and the closed-form `validity_mask`, so no path
re-packs.

The kernel's storage chains are the full memory hierarchy, so only
*no-bypass* mappings are eligible.  Eligibility is detected per mapping:
a ``backend="pallas"`` batch that mixes bypass and no-bypass mappings is
split, the eligible rows scored by the kernel and the rest by the jnp
oracle, and the scores merged back in order — callers never need to
pre-sort a mapspace.  ``backend="auto"`` resolves to ``pallas`` when a TPU
is attached (the kernel then beats per-mapping jnp dispatch) and to
``jnp`` otherwise (interpret mode is a correctness path, not a fast path).

The kernel emits (cycles, energy) only; validity (fanout + buffer-capacity
checks) is closed-form per mapping and computed here with the same
formulas `evaluate_batch_multi` uses, so both backends agree on the valid
set exactly.
"""
from __future__ import annotations

import math
from typing import Optional, Sequence, Tuple

import numpy as np

from .batch_eval import (GOAL_KEY, HwStatic, batch_scores_arrays,
                         make_static, pack, tile_words_np)
from .mapping import Mapping

BACKENDS = ("auto", "jnp", "pallas")


def default_backend() -> str:
    """Concrete engine `auto` resolves to on this host."""
    import jax
    return "pallas" if jax.default_backend() == "tpu" else "jnp"


def resolve_backend(backend: str) -> str:
    """Validate and collapse `auto` to a concrete engine name."""
    if backend not in BACKENDS:
        raise ValueError(
            f"backend must be one of {BACKENDS}, got {backend!r}")
    return default_backend() if backend == "auto" else backend


def default_interpret() -> bool:
    """Pallas interpret mode default: interpret everywhere but real TPU."""
    import jax
    return jax.default_backend() != "tpu"


def device_scope(device=None):
    """Context manager pinning uncommitted dispatches (jnp or kernel) to
    `device` — the per-shard scope of the multi-device fused path.  None
    is a no-op scope, so a single-device shard plan runs the exact
    historical dispatch."""
    import contextlib
    if device is None:
        return contextlib.nullcontext()
    import jax
    return jax.default_device(device)


def pallas_eligible(mapping: Mapping) -> bool:
    """The kernel assumes full storage chains: no tensor bypasses any
    memory level."""
    return all(not b for b in mapping.bypass)


def eligibility_mask(mappings) -> np.ndarray:
    """Per-row kernel eligibility for a Mapping sequence or a
    `PackedMapspace`."""
    from .mapspace_array import PackedMapspace
    if isinstance(mappings, PackedMapspace):
        return mappings.eligible
    return np.fromiter((pallas_eligible(m) for m in mappings), bool,
                       count=len(mappings))


def validity_mask_arrays(st: HwStatic, factors: np.ndarray,
                         store: np.ndarray) -> np.ndarray:
    """Fanout + buffer-capacity validity over packed arrays,
    formula-identical to the checks in `evaluate_batch_multi` (the pallas
    kernel does not emit validity)."""
    f = np.asarray(factors, np.float64)
    store = np.asarray(store)
    B = f.shape[0]
    valid = np.ones((B,), bool)
    for ri, r in enumerate(st.rout_idx):
        valid &= f[:, r, :].prod(axis=1) <= st.fanout[ri]
    tile_at = np.flip(np.cumprod(np.flip(f, 1), axis=1), 1)
    for j, li in enumerate(st.mem_idx):
        if not math.isfinite(st.sizes[j]):
            continue
        words = tile_words_np(st, tile_at[:, li])       # [B, 3]
        used = np.where(store[:, j, :], words, 0.0).sum(axis=1)
        valid &= used <= st.sizes[j]
    return valid


def validity_mask(mappings: Sequence[Mapping]) -> np.ndarray:
    """Object-path wrapper over `validity_mask_arrays` (packs once)."""
    st = make_static(mappings[0].hardware, mappings[0].workload)
    factors, _, store = pack(mappings)
    return validity_mask_arrays(st, factors, store)


def _as_arrays(mappings):
    """Uniform array view of a batch: -> (st, factors, rank, store).
    Packs a Mapping sequence exactly once; a PackedMapspace passes
    through untouched.  Eligibility is NOT computed here — it is an
    O(n) object walk on the legacy path and only the pallas engine
    needs it."""
    from .mapspace_array import PackedMapspace
    if isinstance(mappings, PackedMapspace):
        return (mappings.static, mappings.factors, mappings.rank,
                mappings.store)
    st = make_static(mappings[0].hardware, mappings[0].workload)
    factors, rank, store = pack(mappings)
    return st, factors, rank, store


def _pallas_scores_arrays(st: HwStatic, factors, rank, goal: str,
                          block: int, interpret: Optional[bool]
                          ) -> np.ndarray:
    from ..kernels.mapspace_eval.ops import mapspace_eval_multi
    if interpret is None:
        interpret = default_interpret()
    cycles, energy = mapspace_eval_multi([(st, factors, rank)], block=block,
                                         interpret=interpret)
    if goal == "latency":
        return np.asarray(cycles, np.float64)
    if goal == "energy":
        return np.asarray(energy, np.float64)
    return np.asarray(cycles, np.float64) * np.asarray(energy, np.float64)


def score_mapspace(mappings, goal: str = "edp",
                   backend: str = "auto", *, block: int = 256,
                   interpret: Optional[bool] = None
                   ) -> Tuple[np.ndarray, np.ndarray]:
    """-> (scores [n], valid [n]); lower score is better, invalid rows
    carry their score (mask with `valid` before argmin).

    `mappings` is a `Sequence[Mapping]` or a `PackedMapspace`; the batch
    is one mapspace (one hardware/workload pair).  `backend` is `auto`,
    `jnp`, or `pallas`; the pallas engine scores the no-bypass rows with
    the kernel and falls back to the jnp oracle for the rest.
    """
    from .mapspace_array import PackedMapspace
    is_packed = isinstance(mappings, PackedMapspace)
    if not is_packed:
        mappings = list(mappings)
    if len(mappings) == 0:
        raise ValueError("score_mapspace: empty mapping batch")
    if goal not in GOAL_KEY:
        raise ValueError(f"goal must be one of {sorted(GOAL_KEY)}, "
                         f"got {goal!r}")
    from ..obs import current_tracer
    engine = resolve_backend(backend)
    tr = current_tracer()
    st, factors, rank, store = _as_arrays(mappings)
    # dispatch spans are host-side; the np.asarray conversions inside
    # them force JAX's async dispatch, so device time lands in the span
    if engine == "jnp":
        with tr.span("backend.jnp", rows=int(factors.shape[0])):
            scores, valid = batch_scores_arrays(st, factors, rank, store,
                                                goal)
            scores = np.asarray(scores, np.float64)
            valid = np.asarray(valid, bool)
        tr.metrics.counter("backend.rows.jnp").inc(factors.shape[0])
        return scores, valid

    mask = eligibility_mask(mappings)
    n = factors.shape[0]
    n_kernel = int(mask.sum())
    scores = np.empty((n,), np.float64)
    valid = np.empty((n,), bool)
    with tr.span("backend.pallas", rows=n, kernel_rows=n_kernel,
                 jnp_rows=n - n_kernel):
        if mask.any():
            idx = np.flatnonzero(mask)
            scores[idx] = _pallas_scores_arrays(st, factors[idx],
                                                rank[idx], goal, block,
                                                interpret)
            valid[idx] = validity_mask_arrays(st, factors[idx],
                                              store[idx])
        if not mask.all():
            idx = np.flatnonzero(~mask)
            s, v = batch_scores_arrays(st, factors[idx], rank[idx],
                                       store[idx], goal)
            scores[idx] = np.asarray(s, np.float64)
            valid[idx] = np.asarray(v, bool)
    tr.metrics.counter("backend.rows.kernel").inc(n_kernel)
    tr.metrics.counter("backend.rows.jnp").inc(n - n_kernel)
    return scores, valid


def best_index(mappings, goal: str = "edp",
               backend: str = "auto", *, block: int = 256,
               interpret: Optional[bool] = None) -> int:
    """Index of the goal-best *valid* mapping (ties break low, matching
    `batch_eval.batch_best_index`)."""
    scores, valid = score_mapspace(mappings, goal, backend, block=block,
                                   interpret=interpret)
    return int(np.argmin(np.where(valid, scores, np.inf)))
