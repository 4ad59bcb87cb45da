"""The kernel's per-mapping tensors, derived on the device by the jitted
prologue of `kernel._multi_call`, against a frozen copy of the NumPy host
derivation they replace: the fifteen kernel inputs equal byte for byte,
and the kernel scores both sets to the same bytes (interpret mode)."""
import dataclasses
import functools
import math

import jax
import numpy as np
import pytest

from repro.core import MapperConfig, make_fpga_arch, make_spatial_arch
from repro.core.batch_eval import bucket, sig_of
from repro.core.mapspace_array import build_packed_mapspace
from repro.core.workload import (Workload, conv2d_workload, N_, M_, C_, R_,
                                 S_, E_, F_)
from repro.kernels.mapspace_eval import kernel, ops

# ---------------------------------------------------------------------------
# frozen oracle: the NumPy host derivation, as it stood before the prologue
# ---------------------------------------------------------------------------
_RELEVANT = {
    "input": np.array([1, 0, 1, 1, 1, 1, 1], bool),
    "weight": np.array([0, 1, 1, 1, 1, 0, 0], bool),
    "output": np.array([1, 1, 0, 0, 0, 1, 1], bool),
}
_SLIDING = np.zeros(7, bool)
_SLIDING[[R_, S_, E_, F_]] = True


def _tile_words_np(st, tile):
    n, m, c, r, s, e, f = (tile[..., i] for i in range(7))
    u, v = st.stride
    dr, ds = st.dilation
    p = (e - 1) * u + (r - 1) * dr + 1
    q = (f - 1) * v + (s - 1) * ds + 1
    w = (r * s * c * m) if st.has_weight else np.zeros_like(n)
    o = n * e * f * (c if st.depthwise else m)
    return np.stack([n * c * p * q, w, o], axis=-1)


def _fresh_np(st, tile, d):
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


def _mapping_rows(st, factors, rank):
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

    rel_i = _RELEVANT["input"][slot_dim].astype(np.float32)
    rel_w = _RELEVANT["weight"][slot_dim].astype(np.float32)
    rel_out = _RELEVANT["output"].copy()
    if st.depthwise:
        rel_out = np.array([1, 1, 1, 0, 0, 1, 1], bool)
    rel_o = rel_out[slot_dim].astype(np.float32)

    def inst_before(tiling_idx):
        inst = np.ones((B,), np.float32)
        for r in rout:
            if r < tiling_idx:
                inst *= np.prod(factors[:, r, :], axis=1)
        return inst

    L1 = Lm
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
            if _SLIDING[d]:
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
    return arrays, tuple(zs_parent), Lm, L1


def _hw_numerics(st):
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


def _to_lanes(arrays, block):
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
        out.append(np.ascontiguousarray(a, np.float32))
    return out, block


def _multi_rows(groups):
    sig0 = sig_of(groups[0][0])
    per_group = []
    for st, factors, rank in groups:
        assert sig_of(st) == sig0, "kernel groups must share a BatchSig"
        arrays, zs_parent, Lm, L1 = _mapping_rows(st, factors, rank)
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


# ---------------------------------------------------------------------------
# cases
# ---------------------------------------------------------------------------
def _two_noc_arch():
    """DRAM -> Gbuf -> NoC0 -> NoC1 -> RF -> PE: the Gbuf -> RF pair
    crosses two routing levels, whose energies the float32 sum rounds
    once per level."""
    hw = make_spatial_arch(name="two-noc", num_pes=16, rf_words=64,
                           gbuf_words=4096, zero_skip=True)
    lv = list(hw.levels)
    noc = lv[2]
    lv[2:3] = [dataclasses.replace(noc, name="NoC0", fanout=4,
                                   unicast_energy=2.1, multicast_energy=1.3,
                                   accum_energy=2.7),
               dataclasses.replace(noc, name="NoC1", fanout=4,
                                   unicast_energy=0.7,
                                   multicast_energy=0.35,
                                   accum_energy=0.9)]
    return dataclasses.replace(hw, levels=tuple(lv))


_CONV = conv2d_workload(batch=2, in_ch=4, out_ch=8, out_h=4, out_w=4,
                        kr=3, ks=3, stride=(2, 1), dilation=(1, 2),
                        input_zero_frac=0.25, name="conv")
_POOL = Workload(dims=(2, 1, 8, 2, 2, 4, 4), stride=(2, 2),
                 kind="pool_max", depthwise=True, name="pool")
_ARCHS = {
    "spatial": lambda: make_spatial_arch(name="s16", num_pes=16,
                                         rf_words=64, gbuf_words=4096,
                                         zero_skip=True),
    "spatial-64": lambda: make_spatial_arch(name="s64", num_pes=64,
                                            rf_words=128,
                                            gbuf_words=16 * 1024),
    "fpga": lambda: make_fpga_arch(name="fpga", num_pes=16, cache_kb=16.0),
    "two-noc": _two_noc_arch,
}


@functools.lru_cache(maxsize=None)
def _group(arch: str, wl: str):
    pm = build_packed_mapspace({"conv": _CONV, "pool": _POOL}[wl],
                               _ARCHS[arch](),
                               MapperConfig(enable_bypass=False,
                                            max_mappings=300, seed=1))
    assert len(pm) > 37
    return pm.static, pm.factors, pm.rank


CASES = {
    "spatial": [("spatial", "conv")],
    "fpga": [("fpga", "conv")],
    "depthwise": [("spatial", "pool")],
    "two-archs": [("spatial", "conv"), ("spatial-64", "conv"),
                  ("spatial", "conv")],
    "pad-37": [("spatial-64", "conv", 37)],
    "two-noc": [("two-noc", "conv")],
}


def _groups(case):
    out = []
    for arch, wl, *rows in CASES[case]:
        st, f, r = _group(arch, wl)
        k = rows[0] if rows else len(f)
        out.append((st, f[:k], r[:k]))
    return out


def _bits(a):
    a = np.asarray(a)
    assert a.dtype == np.float32, a.dtype
    return a.view(np.uint32)


@pytest.mark.parametrize("case", list(CASES))
def test_prologue_matches_host_derivation(case):
    groups = _groups(case)
    block = 64
    want, want_static, n = _multi_rows(groups)
    want, want_block = _to_lanes(want, block)

    arrays, static, n_rows = ops._multi_rows(groups)
    assert n_rows == n and arrays[0].shape[-1] == bucket(n)
    got = jax.jit(kernel._kernel_inputs, static_argnames="sig")(
        *arrays, sig=static["sig"])
    assert len(got) == len(want) == 15
    for k, (g, w) in enumerate(zip(got, want)):
        assert g.shape == w.shape, (k, g.shape, w.shape)
        np.testing.assert_array_equal(_bits(g), _bits(w), err_msg=str(k))

    # the same kernel on both sets of inputs, and the whole entry point
    ref = kernel._score(*want, n_mem=want_static["n_mem"],
                        block=want_block, interpret=True)
    cycles, energy = ops.mapspace_eval_multi(groups, block=block,
                                             interpret=True)
    np.testing.assert_array_equal(_bits(cycles), _bits(ref[0][0, :n]))
    np.testing.assert_array_equal(_bits(energy), _bits(ref[1][0, :n]))
    assert np.isfinite(cycles).all() and (energy > 0).all()

    # the copy to the device carries the compact rows, not the tensors
    if static["sig"].n_levels == 4:
        assert sum(a.nbytes for a in arrays) / arrays[0].shape[-1] < 400
