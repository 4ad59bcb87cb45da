"""Vectorized TRIM evaluator: score a *batch* of mappings as one JAX program.

This is the TPU-native rethink of the paper's hot loop (DESIGN.md §3.1):
instead of iterating mappings in Python (Timeloop-style), a mapspace is
packed into integer tensors

    factors [B, L, 7]   loop bounds per tiling level per dim
    rank    [B, L, 7]   position of each dim in the level's loop order
                        (0 = outermost; irrelevant for routing levels)
    store   [B, Lm, 3]  which tensors each memory level stages (bypass)

and the whole evaluator (tile extents, buffer validity, delivery counts with
halo credit, psum read-modify-write, NoC classification, cycles, energy,
EDP) is closed-form batched arithmetic.  Semantics match
`evaluator.evaluate_mapping` exactly — asserted by tests/test_batch_eval.py.

The per-mapping scoring loop is also available as a Pallas TPU kernel
(`repro.kernels.mapspace_eval`) with this module as its oracle; callers
pick an engine through `core.backend.score_mapspace` (backend dispatch
with automatic no-bypass eligibility gating).
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..obs import current_tracer
from .designer import HardwareDesc
from .mapping import Mapping
from .workload import TENSORS, Workload, N_, M_, C_, R_, S_, E_, F_

COMPUTE_CHILD = -1


@dataclasses.dataclass(frozen=True)
class HwStatic:
    """Static (hashable) hardware + workload description for one mapspace."""
    n_levels: int
    mem_idx: Tuple[int, ...]            # tiling indices of memory levels
    rout_idx: Tuple[int, ...]
    sizes: Tuple[float, ...]            # per mem level (inf if unbounded)
    bandwidths: Tuple[float, ...]       # per mem level
    instances: Tuple[int, ...]          # per mem level
    read_e: Tuple[float, ...]
    write_e: Tuple[float, ...]
    leak: Tuple[float, ...]
    fanout: Tuple[int, ...]             # per routing level
    noc_bw: Tuple[float, ...]
    uni_e: Tuple[float, ...]
    multi_e: Tuple[float, ...]
    acc_e: Tuple[float, ...]
    num_pes: int
    macs_per_pe: int
    pipeline: int
    mac_e: float
    pe_leak: float
    zs_boundary: int                    # tiling idx or -1
    # workload
    dims: Tuple[int, ...]
    stride: Tuple[int, int]
    dilation: Tuple[int, int]
    depthwise: bool
    has_weight: bool
    in_zf: float
    w_zf: float


def make_static(hw: HardwareDesc, wl: Workload) -> HwStatic:
    mem = hw.memory_level_indices()
    rout = hw.routing_level_indices()
    lv = hw.tiling_levels
    zs = hw.zero_skip_boundary()
    return HwStatic(
        n_levels=len(lv), mem_idx=tuple(mem), rout_idx=tuple(rout),
        sizes=tuple(float(lv[i].size_words) if lv[i].size_words else
                    float("inf") for i in mem),
        bandwidths=tuple(lv[i].bandwidth for i in mem),
        instances=tuple(hw.instances(i) for i in mem),
        read_e=tuple(lv[i].read_energy for i in mem),
        write_e=tuple(lv[i].write_energy for i in mem),
        leak=tuple(lv[i].leak_power * hw.instances(i) for i in mem),
        fanout=tuple(lv[i].fanout for i in rout),
        noc_bw=tuple(lv[i].bandwidth for i in rout),
        uni_e=tuple(lv[i].unicast_energy for i in rout),
        multi_e=tuple(lv[i].multicast_energy for i in rout),
        acc_e=tuple(lv[i].accum_energy for i in rout),
        num_pes=hw.compute.num_pes, macs_per_pe=hw.compute.macs_per_pe,
        pipeline=hw.compute.pipeline, mac_e=hw.compute.mac_energy,
        pe_leak=hw.compute.pe_leak,
        zs_boundary=-1 if zs is None else zs,
        dims=tuple(wl.dims), stride=tuple(wl.stride),
        dilation=tuple(wl.dilation), depthwise=wl.depthwise,
        has_weight=wl.has_weight, in_zf=wl.input_zero_frac,
        w_zf=wl.weight_zero_frac)


def pack(mappings: Sequence[Mapping]):
    """Mapping objects -> (factors, rank, store) packed *host* arrays.

    Returns numpy: every consumer either feeds a jit boundary (which
    accepts numpy directly) or wants numpy for closed-form host math —
    returning device arrays here forced a numpy->device->numpy
    round-trip on the object path (flagged by trimlint R-SYNC)."""
    hw = mappings[0].hardware
    L = len(hw.tiling_levels)
    mem = hw.memory_level_indices()
    B = len(mappings)
    factors = np.ones((B, L, 7), np.int32)
    rank = np.zeros((B, L, 7), np.int32)
    store = np.ones((B, len(mem), 3), bool)
    for b, m in enumerate(mappings):
        for l in range(L):
            factors[b, l] = m.factors[l]
            order = m.orders[l]
            if order is not None:
                for pos, d in enumerate(order):
                    rank[b, l, d] = pos
        for j, li in enumerate(mem):
            for ti, t in enumerate(TENSORS):
                store[b, j, ti] = m.stores(li, t) or li == 0
    return factors, rank, store


# ---------------------------------------------------------------------------
def _tensor_tile_words(st: HwStatic, tile):
    """tile: [..., 7] float -> dict tensor -> [...] words."""
    n, m, c, r, s, e, f = (tile[..., i] for i in range(7))
    u, v = st.stride
    dr, ds = st.dilation
    p = (e - 1) * u + (r - 1) * dr + 1
    q = (f - 1) * v + (s - 1) * ds + 1
    return {
        "input": n * c * p * q,
        "weight": (r * s * c * m) if st.has_weight else jnp.zeros_like(n),
        "output": n * e * f * (c if st.depthwise else m),
    }


def _fresh_input_words(st: HwStatic, tile, slide_dim):
    """Fresh input words for one slide step along slide_dim [..., int]."""
    n, m, c, r, s, e, f = (tile[..., i] for i in range(7))
    u, v = st.stride
    dr, ds = st.dilation
    p = (e - 1) * u + (r - 1) * dr + 1
    q = (f - 1) * v + (s - 1) * ds + 1
    fr_e = n * c * jnp.minimum(p, e * u) * q
    fr_f = n * c * p * jnp.minimum(q, f * v)
    fr_r = n * c * jnp.minimum(p, r * dr) * q
    fr_s = n * c * p * jnp.minimum(q, s * ds)
    out = jnp.where(slide_dim == E_, fr_e,
                    jnp.where(slide_dim == F_, fr_f,
                              jnp.where(slide_dim == R_, fr_r, fr_s)))
    return out


RELEVANT = {
    "input": np.array([1, 0, 1, 1, 1, 1, 1], bool),
    "weight": np.array([0, 1, 1, 1, 1, 0, 0], bool),
    "output": np.array([1, 1, 0, 0, 0, 1, 1], bool),
}
SLIDING = np.zeros(7, bool)
SLIDING[[R_, S_, E_, F_]] = True

GOAL_KEY = {"latency": "cycles", "energy": "energy_pj", "edp": "edp"}


def tile_words_np(st: HwStatic, tile):
    """tile: [..., 7] float -> [..., 3] words in TENSORS order.  Numpy
    twin of `_tensor_tile_words`, shared by the kernel packer
    (kernels/mapspace_eval/ops.py) and `core.backend.validity_mask` so
    the halo/depthwise/has-weight formulas exist exactly twice (jnp +
    np), not once per consumer."""
    n, m, c, r, s, e, f = (tile[..., i] for i in range(7))
    u, v = st.stride
    dr, ds = st.dilation
    p = (e - 1) * u + (r - 1) * dr + 1
    q = (f - 1) * v + (s - 1) * ds + 1
    w = (r * s * c * m) if st.has_weight else np.zeros_like(n)
    o = n * e * f * (c if st.depthwise else m)
    return np.stack([n * c * p * q, w, o], axis=-1)


@functools.partial(jax.jit, static_argnums=0)
def evaluate_batch(st: HwStatic, factors, rank, store):
    """-> dict of [B] arrays: cycles, dynamic_pj, static_pj, energy_pj, edp,
    valid, pes_used."""
    B, L, _ = factors.shape
    f32 = factors.astype(jnp.float64 if jax.config.jax_enable_x64
                         else jnp.float32)
    mem = list(st.mem_idx)
    Lm = len(mem)

    # ---- tiles: tile_at[:, l] = prod_{l' >= l} factors -------------------
    rev = jnp.flip(f32, axis=1)
    tile_at = jnp.flip(jnp.cumprod(rev, axis=1), axis=1)       # [B, L, 7]
    tile_at = jnp.concatenate([tile_at, jnp.ones((B, 1, 7), f32.dtype)],
                              axis=1)                          # [B, L+1, 7]

    # ---- flattened temporal loop slots -----------------------------------
    # slot order: (memory level asc, rank within level asc)
    n_slots = Lm * 7
    slot_bound = jnp.ones((B, n_slots), f32.dtype)
    slot_dim = jnp.zeros((B, n_slots), jnp.int32)
    for j, li in enumerate(mem):
        pos = rank[:, li, :]                                   # [B, 7]
        base = j * 7
        idx = base + pos                                       # [B, 7]
        slot_bound = jax.vmap(lambda sb, ix, fv: sb.at[ix].set(fv))(
            slot_bound, idx, f32[:, li, :])
        slot_dim = jax.vmap(lambda sd, ix: sd.at[ix].set(
            jnp.arange(7, dtype=jnp.int32)))(slot_dim, idx)
    active = slot_bound > 1.0                                  # [B, n_slots]
    cum = jnp.cumprod(slot_bound, axis=1)                      # [B, n_slots]

    rel_t = {t: jnp.asarray(RELEVANT[t]) for t in TENSORS}
    if st.depthwise:
        rel_t["output"] = jnp.asarray(
            np.array([1, 1, 1, 0, 0, 1, 1], bool))
    sliding = jnp.asarray(SLIDING)

    rout = list(st.rout_idx)
    rout_prod = [jnp.prod(f32[:, r, :], axis=1) for r in rout]   # [B] each

    def inst_before(tiling_idx_arr):
        """Used instances outer than (data-dependent) tiling index [B]."""
        inst = jnp.ones((B,), f32.dtype)
        for ri, r in enumerate(rout):
            inst = inst * jnp.where(tiling_idx_arr > r, rout_prod[ri], 1.0)
        return inst

    def spatial_between(parent_tiling, child_tiling_static):
        """Per-dim routing factors with parent < r < child. [B, 7]."""
        S = jnp.ones((B, 7), f32.dtype)
        for ri, r in enumerate(rout):
            if r < child_tiling_static:
                m = (parent_tiling < r)[:, None]
                S = S * jnp.where(m, f32[:, r, :], 1.0)
        return S

    def scan_pair(child_j, tensor, parent_tiling):
        """Traffic for chain pair into child at mem position child_j
        (child_j == Lm means COMPUTE).  Returns dict of [B] arrays."""
        if child_j == Lm:
            per_inst = jnp.ones((B, 7), f32.dtype)
            child_tiling = st.n_levels
            n_vis = n_slots
        else:
            per_inst = tile_at[:, mem[child_j]]
            child_tiling = mem[child_j]
            n_vis = child_j * 7
        S = spatial_between(parent_tiling, child_tiling)
        union = per_inst * S
        pw = _tensor_tile_words(st, per_inst)[tensor]
        uw = _tensor_tile_words(st, union)[tensor]
        i_a = inst_before(parent_tiling)
        i_b = inst_before(jnp.full((B,), child_tiling))
        zero = jnp.zeros((B,), f32.dtype)
        if n_vis == 0:
            V = jnp.ones((B,), f32.dtype)
            D = V
            union_words = uw
            has = jnp.zeros((B,), bool)
        else:
            rel = rel_t[tensor][slot_dim[:, :n_vis]] & active[:, :n_vis]
            pos = jnp.arange(1, n_vis + 1)
            k1 = jnp.max(jnp.where(rel, pos, 0), axis=1)       # 1-based
            has = k1 > 0
            kidx = jnp.maximum(k1 - 1, 0)
            P_k = jnp.take_along_axis(cum[:, :n_vis], kidx[:, None],
                                      axis=1)[:, 0]
            b_k = jnp.take_along_axis(slot_bound[:, :n_vis], kidx[:, None],
                                      axis=1)[:, 0]
            d_k = jnp.take_along_axis(slot_dim[:, :n_vis], kidx[:, None],
                                      axis=1)[:, 0]
            outer = P_k / b_k
            V = jnp.where(has, P_k, 1.0)
            relb = rel & (pos[None, :] <= k1[:, None])
            D = jnp.prod(jnp.where(relb, slot_bound[:, :n_vis], 1.0), axis=1)
            D = jnp.where(has, D, 1.0)
            union_words = V * uw
            if tensor == "input" and child_j != Lm:
                fresh = _fresh_input_words(st, union, d_k)
                slid = outer * (uw + (b_k - 1) * fresh)
                union_words = jnp.where(has & sliding[d_k], slid,
                                        union_words)
        if tensor == "output":
            return {"parent_read": i_a * (V - D) * uw,
                    "parent_write": i_a * V * uw,
                    "child_read": zero if child_j == Lm else i_b * V * pw,
                    "child_write": zero if child_j == Lm
                    else i_b * (V - D) * pw,
                    "noc": i_b * (2 * V - D) * pw}
        return {"parent_read": i_a * union_words,
                "parent_write": zero,
                "child_read": zero,
                "child_write": zero if child_j == Lm else i_b * V * pw,
                "noc": i_a * union_words}

    # ---- chain pairs: reads/writes per memory level ----------------------
    reads = [jnp.zeros((B,), f32.dtype) for _ in range(Lm)]
    writes = [jnp.zeros((B,), f32.dtype) for _ in range(Lm)]
    raw = [jnp.zeros((B,), f32.dtype) for _ in range(Lm)]
    # crossing words per routing level per class
    n_r = len(st.rout_idx)
    uni = jnp.zeros((B,), f32.dtype)
    multi = jnp.zeros((B,), f32.dtype)
    acc = jnp.zeros((B,), f32.dtype)
    noc_raw = jnp.zeros((B,), f32.dtype)
    spatial = [f32[:, r, :] for r in st.rout_idx]              # [B,7] each
    m_w = [jnp.any(s[:, jnp.asarray([N_, E_, F_])] > 1, axis=1)
           for s in spatial]
    m_i = [spatial[i][:, M_] > 1 for i in range(n_r)]
    a_o = [jnp.any(s[:, jnp.asarray([C_, R_, S_])] > 1, axis=1)
           for s in spatial]

    zf = {"input": 1.0 - st.in_zf,
          "weight": 1.0 - (st.w_zf if st.has_weight else 0.0),
          "output": 1.0}

    tensors = ["input", "output"] + (["weight"] if st.has_weight else [])
    for ti, tensor in enumerate(TENSORS):
        if tensor not in tensors:
            continue
        st_flag = store[:, :, ti]                              # [B, Lm]
        for child_j in list(range(1, Lm)) + [Lm]:
            if child_j < Lm:
                stores_child = st_flag[:, child_j]
            else:
                stores_child = jnp.ones((B,), bool)
            # parent = largest storing mem position < child_j
            cand = st_flag[:, :child_j]
            ppos = jnp.max(jnp.where(cand,
                                     jnp.arange(child_j)[None, :], 0),
                           axis=1)                             # [B]
            parent_tiling = jnp.asarray(mem)[ppos]
            stats = scan_pair(child_j, tensor, parent_tiling)
            zs_f = jnp.where(
                (st.zs_boundary >= 0) & (parent_tiling >= st.zs_boundary)
                & (tensor != "output"), zf[tensor], 1.0)
            gate0 = stores_child.astype(f32.dtype)
            gate = gate0 * zs_f
            for j in range(Lm):
                sel = (ppos == j).astype(f32.dtype)
                reads[j] = reads[j] + sel * gate * stats["parent_read"]
                writes[j] = writes[j] + sel * gate * stats["parent_write"]
                raw[j] = raw[j] + sel * gate0 * (stats["parent_read"]
                                                 + stats["parent_write"])
            if child_j < Lm:
                writes[child_j] = writes[child_j] \
                    + gate * stats["child_write"]
                reads[child_j] = reads[child_j] + gate * stats["child_read"]
                raw[child_j] = raw[child_j] + gate0 * (
                    stats["child_write"] + stats["child_read"])
            # routing crossings: parent_tiling < r < child_tiling
            child_tiling = (mem[child_j] if child_j < Lm else st.n_levels)
            w = gate * stats["noc"]
            w_raw = gate0 * stats["noc"]
            for ri, r in enumerate(st.rout_idx):
                crosses = (parent_tiling < r) & (r < child_tiling)
                wc = jnp.where(crosses, w, 0.0)
                noc_raw = noc_raw + jnp.where(crosses, w_raw, 0.0)
                if tensor == "weight":
                    uni = uni + jnp.where(m_w[ri], 0.0, wc)
                    multi = multi + jnp.where(m_w[ri], wc, 0.0)
                elif tensor == "input":
                    uni = uni + jnp.where(m_i[ri], 0.0, wc)
                    multi = multi + jnp.where(m_i[ri], wc, 0.0)
                else:
                    uni = uni + jnp.where(a_o[ri], 0.0, wc)
                    acc = acc + jnp.where(a_o[ri], wc, 0.0)

    # ---- cycles / energy ---------------------------------------------------
    macs = float(math.prod(st.dims))
    pes_used = jnp.prod(jnp.stack([jnp.prod(s, axis=1) for s in spatial],
                                  axis=0), axis=0) if spatial else \
        jnp.ones((B,), f32.dtype)
    comp_cycles = macs / (jnp.maximum(pes_used, 1.0)
                          * st.macs_per_pe * st.pipeline)
    cycles = comp_cycles
    dyn = jnp.full((B,), macs * zf["input"] * zf["weight"] * st.mac_e
                   if st.zs_boundary >= 0 else macs * st.mac_e, f32.dtype)
    leak_rate = st.pe_leak * st.num_pes
    for j in range(Lm):
        inst_j = inst_before(jnp.full((B,), mem[j]))
        cycles = jnp.maximum(cycles, raw[j] / (st.bandwidths[j] * inst_j))
        dyn = dyn + reads[j] * st.read_e[j] + writes[j] * st.write_e[j]
        leak_rate = leak_rate + st.leak[j]
    for ri in range(n_r):
        cycles = jnp.maximum(cycles, noc_raw / st.noc_bw[ri])
        dyn = dyn + (uni * st.uni_e[ri] + multi * st.multi_e[ri]
                     + acc * st.acc_e[ri])
    static = leak_rate * cycles
    energy = dyn + static

    # ---- validity ----------------------------------------------------------
    valid = jnp.ones((B,), bool)
    for ri, r in enumerate(st.rout_idx):
        valid &= jnp.prod(f32[:, r, :], axis=1) <= st.fanout[ri]
    for j, li in enumerate(mem):
        if not math.isfinite(st.sizes[j]):
            continue
        tw = _tensor_tile_words(st, tile_at[:, li])
        used = jnp.zeros((B,), f32.dtype)
        for ti, t in enumerate(TENSORS):
            used = used + jnp.where(store[:, j, ti], tw[t], 0.0)
        valid &= used <= st.sizes[j]

    return {"cycles": cycles, "dynamic_pj": dyn, "static_pj": static,
            "energy_pj": energy, "edp": cycles * energy, "valid": valid,
            "pes_used": pes_used}


def _bucket(n: int) -> int:
    """Pad the mapping-batch axis to power-of-2 buckets so jit compiles a
    bounded number of variants (keeps the XLA code cache small across the
    thousands of mapspaces a DSE run evaluates)."""
    b = 256
    while b < n:
        b *= 2
    return b


bucket = _bucket


# ---------------------------------------------------------------------------
# Multi-device sharding + jit-dispatch visibility.
#
# Fused groups are row-wise independent (every output row depends only on
# its own factors/rank/store/params row), so a giant BatchSig group can be
# split along the mapping axis into one contiguous shard per local device,
# each padded to its own power-of-2 bucket, and the host merge concatenates
# per-shard results — bit-identical to the single-call path.  The registry
# below mirrors jit's compile cache per (sig, bucket, device) so recompile
# churn from sharding/bucketing is observable (`summary()['jit']`) instead
# of guessed.
# ---------------------------------------------------------------------------
SHARD_MIN_ROWS = 4096   # below this, sharding overhead beats the win


def shard_bounds(n: int, k: int,
                 min_rows: int = SHARD_MIN_ROWS) -> List[Tuple[int, int]]:
    """Split `n` rows into at most `k` contiguous (lo, hi) shards of
    near-equal size, never creating a shard smaller than `min_rows`
    (small groups stay whole — per-device dispatch overhead and the
    extra per-device compile would dominate).  Always returns at least
    one shard covering [0, n)."""
    if n <= 0:
        return [(0, max(n, 0))]
    k = max(1, min(k, n // max(1, min_rows)))
    if k <= 1:
        return [(0, n)]
    base, extra = divmod(n, k)
    bounds: List[Tuple[int, int]] = []
    lo = 0
    for i in range(k):
        hi = lo + base + (1 if i < extra else 0)
        bounds.append((lo, hi))
        lo = hi
    return bounds


def score_devices() -> Tuple:
    """Local devices available to the fused scorer, in jax order."""
    return tuple(jax.local_devices())


def note_batch_dispatch(bucket_rows: int) -> None:
    """Record one fused-batch dispatch into the ambient tracer's metrics:
    `jit.dispatches` and the `jit.bucket_rows` histogram of padded sizes.
    Real compiles are JAX's own events (`repro.obs.jax_events`)."""
    m = current_tracer().metrics
    m.counter("jit.dispatches").inc()
    m.histogram("jit.bucket_rows").observe(float(bucket_rows))


# ---------------------------------------------------------------------------
# Multi-architecture fused batches (repro.search.batch_frontier).
#
# `evaluate_batch` bakes every hardware constant into the jit closure via the
# static HwStatic, so each (arch, workload) pair compiles and dispatches its
# own program.  For cross-architecture DSE the numeric constants (capacities,
# bandwidths, energies, workload bounds) become per-mapping *arrays* instead,
# and only the structural shape of the evaluation — level layout, tensor set,
# depthwise semantics — stays static.  Mapspaces of any two architectures
# sharing a BatchSig then pack into a single device call.
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class BatchSig:
    """Structural signature of an evaluation: everything `evaluate_batch`
    uses for control flow / array shapes, nothing it uses as a number."""
    n_levels: int
    mem_idx: Tuple[int, ...]
    rout_idx: Tuple[int, ...]
    depthwise: bool
    has_weight: bool


def sig_of(st: HwStatic) -> BatchSig:
    return BatchSig(n_levels=st.n_levels, mem_idx=st.mem_idx,
                    rout_idx=st.rout_idx, depthwise=st.depthwise,
                    has_weight=st.has_weight)


def params_of(st: HwStatic, n: int):
    """Numeric side of `st`, broadcast to [n, ...] arrays (one row per
    mapping) so fused batches can mix architectures and workloads."""
    rep = lambda v: np.broadcast_to(np.asarray(v, np.float32), (n,) +
                                    np.asarray(v, np.float32).shape).copy()
    return {
        "sizes": rep(st.sizes), "bandwidths": rep(st.bandwidths),
        "read_e": rep(st.read_e), "write_e": rep(st.write_e),
        "leak": rep(st.leak),
        "fanout": rep([float(f) for f in st.fanout]),
        "noc_bw": rep(st.noc_bw), "uni_e": rep(st.uni_e),
        "multi_e": rep(st.multi_e), "acc_e": rep(st.acc_e),
        "macs_per_pe": rep(float(st.macs_per_pe)),
        "pipeline": rep(float(st.pipeline)), "mac_e": rep(st.mac_e),
        "pe_leak_total": rep(st.pe_leak * st.num_pes),
        "zs_boundary": np.full((n,), st.zs_boundary, np.int32),
        "macs": rep(float(math.prod(st.dims))),
        "stride": rep([float(s) for s in st.stride]),
        "dilation": rep([float(d) for d in st.dilation]),
        "in_zf": rep(st.in_zf), "w_zf": rep(st.w_zf),
    }


def _tile_words_b(sig: BatchSig, stride, dilation, tile):
    """tile: [B, 7] -> dict tensor -> [B] words; stride/dilation [B, 2]."""
    n, m, c, r, s, e, f = (tile[..., i] for i in range(7))
    u, v = stride[:, 0], stride[:, 1]
    dr, ds = dilation[:, 0], dilation[:, 1]
    p = (e - 1) * u + (r - 1) * dr + 1
    q = (f - 1) * v + (s - 1) * ds + 1
    return {
        "input": n * c * p * q,
        "weight": (r * s * c * m) if sig.has_weight else jnp.zeros_like(n),
        "output": n * e * f * (c if sig.depthwise else m),
    }


def _fresh_input_words_b(stride, dilation, tile, slide_dim):
    n, m, c, r, s, e, f = (tile[..., i] for i in range(7))
    u, v = stride[:, 0], stride[:, 1]
    dr, ds = dilation[:, 0], dilation[:, 1]
    p = (e - 1) * u + (r - 1) * dr + 1
    q = (f - 1) * v + (s - 1) * ds + 1
    fr_e = n * c * jnp.minimum(p, e * u) * q
    fr_f = n * c * p * jnp.minimum(q, f * v)
    fr_r = n * c * jnp.minimum(p, r * dr) * q
    fr_s = n * c * p * jnp.minimum(q, s * ds)
    return jnp.where(slide_dim == E_, fr_e,
                     jnp.where(slide_dim == F_, fr_f,
                               jnp.where(slide_dim == R_, fr_r, fr_s)))


@functools.partial(jax.jit, static_argnums=0)
def evaluate_batch_multi(sig: BatchSig, params, factors, rank, store):
    """`evaluate_batch` with per-mapping hardware/workload constants.

    Semantics match `evaluate_batch` row-for-row when every row carries the
    same architecture (asserted by tests/test_search.py); rows may mix any
    architectures/workloads that share `sig`.
    """
    B, L, _ = factors.shape
    f32 = factors.astype(jnp.float64 if jax.config.jax_enable_x64
                         else jnp.float32)
    cast = lambda k: params[k].astype(f32.dtype)
    sizes, bandwidths = cast("sizes"), cast("bandwidths")
    read_e, write_e, leak = cast("read_e"), cast("write_e"), cast("leak")
    fanout, noc_bw = cast("fanout"), cast("noc_bw")
    uni_e, multi_e, acc_e = cast("uni_e"), cast("multi_e"), cast("acc_e")
    stride, dilation = cast("stride"), cast("dilation")
    macs, mac_e = cast("macs"), cast("mac_e")
    zs_b = params["zs_boundary"]
    mem = list(sig.mem_idx)
    Lm = len(mem)

    rev = jnp.flip(f32, axis=1)
    tile_at = jnp.flip(jnp.cumprod(rev, axis=1), axis=1)
    tile_at = jnp.concatenate([tile_at, jnp.ones((B, 1, 7), f32.dtype)],
                              axis=1)

    n_slots = Lm * 7
    slot_bound = jnp.ones((B, n_slots), f32.dtype)
    slot_dim = jnp.zeros((B, n_slots), jnp.int32)
    for j, li in enumerate(mem):
        pos = rank[:, li, :]
        idx = j * 7 + pos
        slot_bound = jax.vmap(lambda sb, ix, fv: sb.at[ix].set(fv))(
            slot_bound, idx, f32[:, li, :])
        slot_dim = jax.vmap(lambda sd, ix: sd.at[ix].set(
            jnp.arange(7, dtype=jnp.int32)))(slot_dim, idx)
    active = slot_bound > 1.0
    cum = jnp.cumprod(slot_bound, axis=1)

    rel_t = {t: jnp.asarray(RELEVANT[t]) for t in TENSORS}
    if sig.depthwise:
        rel_t["output"] = jnp.asarray(np.array([1, 1, 1, 0, 0, 1, 1], bool))
    sliding = jnp.asarray(SLIDING)

    rout = list(sig.rout_idx)
    rout_prod = [jnp.prod(f32[:, r, :], axis=1) for r in rout]

    def inst_before(tiling_idx_arr):
        inst = jnp.ones((B,), f32.dtype)
        for ri, r in enumerate(rout):
            inst = inst * jnp.where(tiling_idx_arr > r, rout_prod[ri], 1.0)
        return inst

    def spatial_between(parent_tiling, child_tiling_static):
        S = jnp.ones((B, 7), f32.dtype)
        for ri, r in enumerate(rout):
            if r < child_tiling_static:
                m = (parent_tiling < r)[:, None]
                S = S * jnp.where(m, f32[:, r, :], 1.0)
        return S

    def scan_pair(child_j, tensor, parent_tiling):
        if child_j == Lm:
            per_inst = jnp.ones((B, 7), f32.dtype)
            child_tiling = sig.n_levels
            n_vis = n_slots
        else:
            per_inst = tile_at[:, mem[child_j]]
            child_tiling = mem[child_j]
            n_vis = child_j * 7
        S = spatial_between(parent_tiling, child_tiling)
        union = per_inst * S
        pw = _tile_words_b(sig, stride, dilation, per_inst)[tensor]
        uw = _tile_words_b(sig, stride, dilation, union)[tensor]
        i_a = inst_before(parent_tiling)
        i_b = inst_before(jnp.full((B,), child_tiling))
        zero = jnp.zeros((B,), f32.dtype)
        if n_vis == 0:
            V = jnp.ones((B,), f32.dtype)
            D = V
            union_words = uw
            has = jnp.zeros((B,), bool)
        else:
            rel = rel_t[tensor][slot_dim[:, :n_vis]] & active[:, :n_vis]
            pos = jnp.arange(1, n_vis + 1)
            k1 = jnp.max(jnp.where(rel, pos, 0), axis=1)
            has = k1 > 0
            kidx = jnp.maximum(k1 - 1, 0)
            P_k = jnp.take_along_axis(cum[:, :n_vis], kidx[:, None],
                                      axis=1)[:, 0]
            b_k = jnp.take_along_axis(slot_bound[:, :n_vis], kidx[:, None],
                                      axis=1)[:, 0]
            d_k = jnp.take_along_axis(slot_dim[:, :n_vis], kidx[:, None],
                                      axis=1)[:, 0]
            outer = P_k / b_k
            V = jnp.where(has, P_k, 1.0)
            relb = rel & (pos[None, :] <= k1[:, None])
            D = jnp.prod(jnp.where(relb, slot_bound[:, :n_vis], 1.0), axis=1)
            D = jnp.where(has, D, 1.0)
            union_words = V * uw
            if tensor == "input" and child_j != Lm:
                fresh = _fresh_input_words_b(stride, dilation, union, d_k)
                slid = outer * (uw + (b_k - 1) * fresh)
                union_words = jnp.where(has & sliding[d_k], slid,
                                        union_words)
        if tensor == "output":
            return {"parent_read": i_a * (V - D) * uw,
                    "parent_write": i_a * V * uw,
                    "child_read": zero if child_j == Lm else i_b * V * pw,
                    "child_write": zero if child_j == Lm
                    else i_b * (V - D) * pw,
                    "noc": i_b * (2 * V - D) * pw}
        return {"parent_read": i_a * union_words,
                "parent_write": zero,
                "child_read": zero,
                "child_write": zero if child_j == Lm else i_b * V * pw,
                "noc": i_a * union_words}

    reads = [jnp.zeros((B,), f32.dtype) for _ in range(Lm)]
    writes = [jnp.zeros((B,), f32.dtype) for _ in range(Lm)]
    raw = [jnp.zeros((B,), f32.dtype) for _ in range(Lm)]
    n_r = len(rout)
    uni = jnp.zeros((B,), f32.dtype)
    multi = jnp.zeros((B,), f32.dtype)
    acc = jnp.zeros((B,), f32.dtype)
    noc_raw = jnp.zeros((B,), f32.dtype)
    spatial = [f32[:, r, :] for r in rout]
    m_w = [jnp.any(s[:, jnp.asarray([N_, E_, F_])] > 1, axis=1)
           for s in spatial]
    m_i = [spatial[i][:, M_] > 1 for i in range(n_r)]
    a_o = [jnp.any(s[:, jnp.asarray([C_, R_, S_])] > 1, axis=1)
           for s in spatial]

    one = jnp.ones((B,), f32.dtype)
    zf = {"input": 1.0 - cast("in_zf"),
          "weight": (1.0 - cast("w_zf")) if sig.has_weight else one,
          "output": one}

    tensors = ["input", "output"] + (["weight"] if sig.has_weight else [])
    for ti, tensor in enumerate(TENSORS):
        if tensor not in tensors:
            continue
        st_flag = store[:, :, ti]
        for child_j in list(range(1, Lm)) + [Lm]:
            if child_j < Lm:
                stores_child = st_flag[:, child_j]
            else:
                stores_child = jnp.ones((B,), bool)
            cand = st_flag[:, :child_j]
            ppos = jnp.max(jnp.where(cand,
                                     jnp.arange(child_j)[None, :], 0),
                           axis=1)
            parent_tiling = jnp.asarray(mem)[ppos]
            stats = scan_pair(child_j, tensor, parent_tiling)
            zs_f = jnp.where(
                (zs_b >= 0) & (parent_tiling >= zs_b)
                & (tensor != "output"), zf[tensor], 1.0)
            gate0 = stores_child.astype(f32.dtype)
            gate = gate0 * zs_f
            for j in range(Lm):
                sel = (ppos == j).astype(f32.dtype)
                reads[j] = reads[j] + sel * gate * stats["parent_read"]
                writes[j] = writes[j] + sel * gate * stats["parent_write"]
                raw[j] = raw[j] + sel * gate0 * (stats["parent_read"]
                                                 + stats["parent_write"])
            if child_j < Lm:
                writes[child_j] = writes[child_j] \
                    + gate * stats["child_write"]
                reads[child_j] = reads[child_j] + gate * stats["child_read"]
                raw[child_j] = raw[child_j] + gate0 * (
                    stats["child_write"] + stats["child_read"])
            child_tiling = (mem[child_j] if child_j < Lm else sig.n_levels)
            w = gate * stats["noc"]
            w_raw = gate0 * stats["noc"]
            for ri, r in enumerate(rout):
                crosses = (parent_tiling < r) & (r < child_tiling)
                wc = jnp.where(crosses, w, 0.0)
                noc_raw = noc_raw + jnp.where(crosses, w_raw, 0.0)
                if tensor == "weight":
                    uni = uni + jnp.where(m_w[ri], 0.0, wc)
                    multi = multi + jnp.where(m_w[ri], wc, 0.0)
                elif tensor == "input":
                    uni = uni + jnp.where(m_i[ri], 0.0, wc)
                    multi = multi + jnp.where(m_i[ri], wc, 0.0)
                else:
                    uni = uni + jnp.where(a_o[ri], 0.0, wc)
                    acc = acc + jnp.where(a_o[ri], wc, 0.0)

    pes_used = jnp.prod(jnp.stack([jnp.prod(s, axis=1) for s in spatial],
                                  axis=0), axis=0) if spatial else \
        jnp.ones((B,), f32.dtype)
    comp_cycles = macs / (jnp.maximum(pes_used, 1.0)
                          * cast("macs_per_pe") * cast("pipeline"))
    cycles = comp_cycles
    dyn = macs * jnp.where(zs_b >= 0, zf["input"] * zf["weight"], 1.0) * mac_e
    leak_rate = cast("pe_leak_total")
    for j in range(Lm):
        inst_j = inst_before(jnp.full((B,), mem[j]))
        cycles = jnp.maximum(cycles, raw[j] / (bandwidths[:, j] * inst_j))
        dyn = dyn + reads[j] * read_e[:, j] + writes[j] * write_e[:, j]
        leak_rate = leak_rate + leak[:, j]
    for ri in range(n_r):
        cycles = jnp.maximum(cycles, noc_raw / noc_bw[:, ri])
        dyn = dyn + (uni * uni_e[:, ri] + multi * multi_e[:, ri]
                     + acc * acc_e[:, ri])
    static = leak_rate * cycles
    energy = dyn + static

    valid = jnp.ones((B,), bool)
    for ri, r in enumerate(rout):
        valid &= jnp.prod(f32[:, r, :], axis=1) <= fanout[:, ri]
    for j, li in enumerate(mem):
        tw = _tile_words_b(sig, stride, dilation, tile_at[:, li])
        used = jnp.zeros((B,), f32.dtype)
        for ti, t in enumerate(TENSORS):
            used = used + jnp.where(store[:, j, ti], tw[t], 0.0)
        valid &= used <= sizes[:, j]

    return {"cycles": cycles, "dynamic_pj": dyn, "static_pj": static,
            "energy_pj": energy, "edp": cycles * energy, "valid": valid,
            "pes_used": pes_used}


def batch_scores_arrays(st: HwStatic, factors, rank, store,
                        goal: str = "edp"):
    """`batch_scores` on pre-packed arrays (numpy or jnp); pads the
    mapping axis to a power-of-2 bucket and evaluates one jit call."""
    factors = jnp.asarray(factors)
    rank = jnp.asarray(rank)
    store = jnp.asarray(store)
    n = factors.shape[0]
    pad = _bucket(n) - n
    if pad:
        rep = lambda a: jnp.concatenate(
            [a, jnp.repeat(a[:1], pad, axis=0)], axis=0)
        factors, rank, store = rep(factors), rep(rank), rep(store)
    # the np.asarray forces the async jit dispatch: bracket it in a span
    # so device time is attributable even when no caller holds one open
    # (trimlint R-SYNC — some callers, e.g. batch_scores, are bare)
    with current_tracer().span("batch_eval.scores", rows=int(n)):
        out = evaluate_batch(st, factors, rank, store)
        key = {"latency": "cycles", "energy": "energy_pj",
               "edp": "edp"}[goal]
        return np.asarray(out[key][:n]), np.asarray(out["valid"][:n])


def batch_scores(mappings, goal: str = "edp"):
    """Score a mapspace (a `Sequence[Mapping]` — packed here exactly once
    — or a pre-packed `core.mapspace_array.PackedMapspace`)."""
    from .mapspace_array import PackedMapspace
    if isinstance(mappings, PackedMapspace):
        return batch_scores_arrays(mappings.static, mappings.factors,
                                   mappings.rank, mappings.store, goal)
    st = make_static(mappings[0].hardware, mappings[0].workload)
    factors, rank, store = pack(mappings)
    return batch_scores_arrays(st, factors, rank, store, goal)


def batch_best_index(mappings, goal: str = "edp",
                     backend: str = "jnp") -> int:
    """Index of the goal-best valid mapping; `mappings` is a Mapping
    sequence or a `PackedMapspace`."""
    if backend != "jnp":
        from .backend import best_index     # lazy: backend wraps this module
        return best_index(mappings, goal, backend)
    scores, valid = batch_scores(mappings, goal)
    scores = np.where(valid, scores, np.inf)
    return int(np.argmin(scores))
