"""Jit'd wrappers: packed mapspace arrays -> kernel tensors -> scores.

Precomputes the per-mapping tensors described in kernel.py (cheap numpy)
from packed `(factors, rank)` arrays.  Three entry points:

  * `mapspace_eval(mappings, ...)`        — legacy object API (packs once);
  * `mapspace_eval_arrays(st, f, r, ...)` — pre-packed arrays, one
    hardware/workload pair baked statically (single-arch kernel);
  * `mapspace_eval_multi(groups, ...)`    — cross-architecture batches:
    rows from several `(HwStatic, factors, rank)` groups sharing one
    `BatchSig` fuse into ONE kernel call with per-row hardware constants
    (same contract as `core.batch_eval.evaluate_batch_multi`).

Only no-bypass mappings are accepted (the kernel's storage chains are the
full memory hierarchy); the general path is core.batch_eval, and
`core.backend.score_mapspace` dispatches between the two with per-mapping
eligibility gating.
"""
from __future__ import annotations

import math
from typing import List, Sequence, Tuple

import jax.numpy as jnp
import numpy as np

from ...core.batch_eval import (RELEVANT, SLIDING, HwStatic, bucket,
                                make_static, pack, sig_of,
                                tile_words_np as _tile_words_np)
from ...core.mapping import Mapping
from ...core.workload import N_, M_, C_, R_, S_, E_, F_
from ...obs import current_tracer
from .kernel import mapspace_eval_fwd, mapspace_eval_multi_fwd


def _fresh_np(st: HwStatic, tile, d):
    n, m, c, r, s, e, f = (tile[..., i] for i in range(7))
    u, v = st.stride
    dr, ds = st.dilation
    p = (e - 1) * u + (r - 1) * dr + 1
    q = (f - 1) * v + (s - 1) * ds + 1
    if d == E_:
        return n * c * np.minimum(p, e * u) * q
    if d == F_:
        return n * c * p * np.minimum(q, f * v)
    if d == R_:
        return n * c * np.minimum(p, r * dr) * q
    return n * c * p * np.minimum(q, s * ds)


def _mapping_rows(st: HwStatic, factors: np.ndarray, rank: np.ndarray):
    """The twelve per-mapping kernel tensors (numpy) for one hardware/
    workload pair.  Shared by the single-arch packer (which bakes the
    hardware numerics statically) and the multi-arch packer (which turns
    them into per-row arrays)."""
    factors = np.asarray(factors, np.float32)
    rank = np.asarray(rank)
    B, L, _ = factors.shape
    mem = list(st.mem_idx)
    rout = list(st.rout_idx)
    Lm = len(mem)
    S = Lm * 7

    tile_at = np.flip(np.cumprod(np.flip(factors, 1), axis=1), 1)
    tile_at = np.concatenate([tile_at, np.ones((B, 1, 7), np.float32)], 1)

    slot_bound = np.ones((B, S), np.float32)
    slot_dim = np.zeros((B, S), np.int64)
    for j, li in enumerate(mem):
        for d in range(7):
            idx = j * 7 + rank[:, li, d]
            slot_bound[np.arange(B), idx] = factors[:, li, d]
            slot_dim[np.arange(B), idx] = d
    cum = np.cumprod(slot_bound, axis=1)

    rel_i = RELEVANT["input"][slot_dim].astype(np.float32)
    rel_w = RELEVANT["weight"][slot_dim].astype(np.float32)
    rel_out = RELEVANT["output"].copy()
    if st.depthwise:
        rel_out = np.array([1, 1, 1, 0, 0, 1, 1], bool)
    rel_o = rel_out[slot_dim].astype(np.float32)

    def inst_before(tiling_idx):
        inst = np.ones((B,), np.float32)
        for r in rout:
            if r < tiling_idx:
                inst *= np.prod(factors[:, r, :], axis=1)
        return inst

    L1 = Lm  # children: mem[1..Lm-1] + compute
    tw_u = np.zeros((B, L1, 3), np.float32)
    tw_p = np.zeros((B, L1, 3), np.float32)
    fresh = np.zeros((B, L1, S), np.float32)
    ia = np.zeros((B, L1), np.float32)
    ib = np.zeros((B, L1), np.float32)
    noc_e = np.zeros((B, L1, 3), np.float32)
    noc_m = np.zeros((B, L1), np.float32)
    zs_parent = []
    for jj in range(L1):
        parent_t = mem[jj]
        child_t = mem[jj + 1] if jj + 1 < Lm else st.n_levels
        per = tile_at[:, child_t] if jj + 1 < Lm else \
            np.ones((B, 7), np.float32)
        Sb = np.ones((B, 7), np.float32)
        crossed = [r for r in rout if parent_t < r < child_t]
        for r in crossed:
            Sb *= factors[:, r, :]
        union = per * Sb
        tw_p[:, jj] = _tile_words_np(st, per)
        tw_u[:, jj] = _tile_words_np(st, union)
        ia[:, jj] = inst_before(parent_t)
        ib[:, jj] = inst_before(child_t)
        zs_parent.append(int(st.zs_boundary >= 0
                             and parent_t >= st.zs_boundary))
        for d in range(7):
            if SLIDING[d]:
                fr = _fresh_np(st, union, d)
            else:
                fr = tw_u[:, jj, 0]
            fresh[:, jj, :][slot_dim == d] = np.broadcast_to(
                fr[:, None], (B, S))[slot_dim == d]
        if crossed:
            noc_m[:, jj] = 1.0
            for r in crossed:
                sp = factors[:, r, :]
                m_w = (sp[:, [N_, E_, F_]] > 1).any(1)
                m_i = sp[:, M_] > 1
                a_o = (sp[:, [C_, R_, S_]] > 1).any(1)
                k = rout.index(r)
                noc_e[:, jj, 0] += np.where(m_i, st.multi_e[k], st.uni_e[k])
                noc_e[:, jj, 1] += np.where(m_w, st.multi_e[k], st.uni_e[k])
                noc_e[:, jj, 2] += np.where(a_o, st.acc_e[k], st.uni_e[k])

    arrays = [slot_bound, cum, rel_i, rel_w, rel_o, tw_u, tw_p, fresh,
              ia, ib, noc_e, noc_m]
    return arrays, tuple(zs_parent), Lm, L1, S


def _hw_numerics(st: HwStatic):
    """The scalar hardware/workload numerics the single-arch kernel bakes
    statically (and the multi-arch kernel reads as per-row arrays)."""
    macs = float(math.prod(st.dims))
    nz = (1.0 - st.in_zf) * (1.0 - (st.w_zf if st.has_weight else 0.0))
    eff = macs * nz if st.zs_boundary >= 0 else macs
    zf = (1.0 - st.in_zf,
          1.0 - (st.w_zf if st.has_weight else 0.0), 1.0)
    return dict(
        macs=macs, eff_macs=eff, zf=zf,
        macs_per_pe=float(st.macs_per_pe), pipeline=float(st.pipeline),
        mac_energy=float(st.mac_e),
        leak_rate=float(sum(st.leak) + st.pe_leak * st.num_pes),
        noc_bw=float(st.noc_bw[0]) if st.noc_bw else 1e30,
        mem_bw=tuple(st.bandwidths), e_read=tuple(st.read_e),
        e_write=tuple(st.write_e))


def _to_lanes(arrays, block: int):
    """Row-major per-mapping arrays [B, ...] -> the kernel's feature-major
    layout with the mapping axis last, padded (repeating row 0) to the
    power-of-2 `bucket` the jnp path uses, so row counts reuse compiled
    shapes.  Index 7 is `fresh` [B, L1, S] -> [L1, S, Bp]; every other
    array flattens its features -> [F, Bp].  -> (device arrays, block)."""
    B = arrays[0].shape[0]
    bp = bucket(B)
    block = min(block, bp)
    assert bp % block == 0, (bp, block)
    out = []
    for k, a in enumerate(arrays):
        if bp > B:
            a = np.concatenate([a, np.repeat(a[:1], bp - B, 0)], 0)
        a = (a.transpose(1, 2, 0) if k == 7
             else a.reshape(bp, -1).T)
        out.append(jnp.asarray(np.ascontiguousarray(a, np.float32)))
    return out, block


def pack_for_kernel_arrays(st: HwStatic, factors, rank, block: int = 256):
    """Pre-packed arrays -> (kernel arrays, static dict, n, block) for the
    single-arch kernel."""
    arrays, zs_parent, Lm, L1, _ = _mapping_rows(st, factors, rank)
    B = arrays[0].shape[0]
    hw = _hw_numerics(st)
    static = dict(
        vis=tuple((jj + 1) * 7 for jj in range(L1)),
        mem_bw=hw["mem_bw"], e_read=hw["e_read"], e_write=hw["e_write"],
        zs_parent=zs_parent, zf=hw["zf"],
        macs=hw["macs"], macs_per_pe=hw["macs_per_pe"],
        pipeline=hw["pipeline"], mac_energy=hw["mac_energy"],
        eff_macs=hw["eff_macs"], leak_rate=hw["leak_rate"],
        noc_bw=hw["noc_bw"], n_mem=Lm)
    arrays, block = _to_lanes(arrays, block)
    return arrays, static, B, block


def pack_for_kernel(mappings: Sequence[Mapping], block: int = 256):
    """Legacy object API: packs the mappings once, then defers to
    `pack_for_kernel_arrays`."""
    for m in mappings:
        assert all(not b for b in m.bypass), "kernel path is no-bypass only"
    st = make_static(mappings[0].hardware, mappings[0].workload)
    factors, rank, _ = pack(mappings)
    return pack_for_kernel_arrays(st, factors, rank, block)


def mapspace_eval_arrays(st: HwStatic, factors, rank, *, block: int = 256,
                         interpret: bool = False):
    """-> (cycles [n], energy [n]) float32 arrays from packed arrays."""
    arrays, static, n, block = pack_for_kernel_arrays(st, factors, rank,
                                                      block)
    cycles, energy = mapspace_eval_fwd(*arrays, static=static, block=block,
                                       interpret=interpret)
    return np.asarray(cycles[0, :n]), np.asarray(energy[0, :n])


def mapspace_eval(mappings: Sequence[Mapping], *, block: int = 256,
                  interpret: bool = False):
    """-> (cycles [n], energy [n]) float32 arrays (legacy object API)."""
    arrays, static, n, block = pack_for_kernel(mappings, block)
    cycles, energy = mapspace_eval_fwd(*arrays, static=static, block=block,
                                       interpret=interpret)
    return np.asarray(cycles[0, :n]), np.asarray(energy[0, :n])


# ---------------------------------------------------------------------------
# multi-architecture fused kernel batches
# ---------------------------------------------------------------------------
def _multi_rows(groups: List[Tuple[HwStatic, np.ndarray, np.ndarray]]):
    """Rows of several single-(arch, workload) groups -> the fused
    row-major arrays [B, ...] with per-row hardware constants, the
    kernel's static dict and B (host numpy; see `pack_for_kernel_multi`)."""
    sig0 = sig_of(groups[0][0])
    per_group = []
    for st, factors, rank in groups:
        assert sig_of(st) == sig0, "kernel groups must share a BatchSig"
        arrays, zs_parent, Lm, L1, _ = _mapping_rows(st, factors, rank)
        B = arrays[0].shape[0]
        hw = _hw_numerics(st)
        zsf = np.ones((B, L1, 3), np.float32)
        for jj in range(L1):
            if zs_parent[jj]:
                zsf[:, jj, :] = np.asarray(hw["zf"], np.float32)
        mem_par = np.broadcast_to(
            np.stack([hw["mem_bw"], hw["e_read"], hw["e_write"]],
                     axis=-1).astype(np.float32), (B, Lm, 3)).copy()
        hw_row = np.broadcast_to(np.asarray(
            [hw["macs"] / (hw["macs_per_pe"] * hw["pipeline"]),
             hw["eff_macs"] * hw["mac_energy"],
             hw["leak_rate"], hw["noc_bw"]], np.float32), (B, 4)).copy()
        per_group.append(arrays + [zsf, mem_par, hw_row])
    fused = [np.concatenate(parts, axis=0)
             for parts in zip(*per_group)]
    Lm = len(sig0.mem_idx)
    static = dict(vis=tuple((jj + 1) * 7 for jj in range(Lm)), n_mem=Lm)
    return fused, static, fused[0].shape[0]


def pack_for_kernel_multi(groups: List[Tuple[HwStatic, np.ndarray,
                                             np.ndarray]],
                          block: int = 256):
    """Rows of several single-(arch, workload) groups -> one fused kernel
    batch with per-row hardware constants.

    Every group must share the structural `BatchSig` (level layout,
    tensor set, depthwise) — exactly the `evaluate_batch_multi` contract;
    the numeric hardware/workload constants become per-row arrays (built
    [B, ...] here, handed to the kernel feature-major by `_to_lanes`):

      zsf     [B, L1, 3]  zero-skip factor per chain pair per tensor
      mem_par [B, Lm, 3]  (bandwidth, read_e, write_e) per memory level
      hw_row  [B, 4]      (comp_scale, eff_mac_pj, leak_rate, noc_bw)
                          with comp_scale = macs / (macs_per_pe * pipeline)
    """
    fused, static, B = _multi_rows(groups)
    fused, block = _to_lanes(fused, block)
    return fused, static, B, block


def mapspace_eval_multi(groups: List[Tuple[HwStatic, np.ndarray,
                                           np.ndarray]], *,
                        block: int = 256, interpret: bool = False):
    """-> (cycles [n], energy [n]) over the concatenated group rows, one
    kernel call for the whole cross-architecture batch.  Each step is a
    span of the ambient tracer: `kernel.pack` (host rows), `kernel.transfer`
    (lane layout and copy to the device), `kernel.launch` (the call up to
    its return: trace, lowering, program load, enqueue) and `kernel.fetch`
    (the wait and the copy back)."""
    tr = current_tracer()
    with tr.span("kernel.pack",
                 rows=sum(int(g[1].shape[0]) for g in groups)):
        fused, static, n = _multi_rows(groups)
    with tr.span("kernel.transfer", rows=n) as sp:
        fused, block = _to_lanes(fused, block)
        sp.set(bytes=sum(int(a.nbytes) for a in fused))
    with tr.span("kernel.launch", rows=n):
        cycles, energy = mapspace_eval_multi_fwd(*fused, static=static,
                                                 block=block,
                                                 interpret=interpret)
    with tr.span("kernel.fetch", rows=n):
        return np.asarray(cycles[0, :n]), np.asarray(energy[0, :n])
