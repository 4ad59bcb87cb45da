"""Host wrappers: packed mapspace arrays -> kernel rows -> scores.

The host gathers the packed `(factors, rank)` rows of a call and each
row's hardware/workload constants; the kernel's per-mapping tensors are
derived on the device, in the jitted program that runs the kernel
(kernel.py).  One entry point, `mapspace_eval_multi(groups, ...)`: rows
from one or more `(HwStatic, factors, rank)` groups sharing one
`BatchSig` fuse into ONE kernel call with per-row hardware constants
(same contract as `core.batch_eval.evaluate_batch_multi`); a single
mapspace is a one-group call.

Only no-bypass mappings are accepted (the kernel's storage chains are the
full memory hierarchy); the general path is core.batch_eval, and
`core.backend.score_mapspace` dispatches between the two with per-mapping
eligibility gating.
"""
from __future__ import annotations

import math
from typing import List, Tuple

import jax.numpy as jnp
import numpy as np

from ...core.batch_eval import BatchSig, HwStatic, bucket, sig_of
from ...obs import current_tracer
from .kernel import chain_pairs, const_rows, mapspace_eval_multi_fwd


def _hw_numerics(st: HwStatic):
    """The scalar hardware/workload numerics of one (arch, workload)
    pair, from which `_group_consts` builds the per-row kernel
    constants."""
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


def _noc_table(st: HwStatic, crossed: Tuple[int, ...]) -> List[float]:
    """A chain pair's NoC pJ/word per tensor for each combination of its
    crossed routing levels' multicast (input, weight) or accumulate
    (output) flags, bit i for crossed level i.  Level energies add in
    float64 onto a float32 sum, one rounding per level, as the NumPy
    oracle's float32 array accumulates them."""
    out = []
    for alt in (st.multi_e, st.multi_e, st.acc_e):
        for combo in range(2 ** len(crossed)):
            acc = np.float32(0.0)
            for i, r in enumerate(crossed):
                k = st.rout_idx.index(r)
                acc = np.float32(float(acc) + (alt[k] if combo >> i & 1
                                               else st.uni_e[k]))
            out.append(float(acc))
    return out


def _group_consts(st: HwStatic, sig: BatchSig) -> np.ndarray:
    """One group's per-row constants [C] in `kernel.const_rows` order:
    geometry (u, v, dr, ds), NoC tables, then

      zsf     [L1*3]  zero-skip factor per chain pair per tensor
      mem_par [Lm*3]  (bandwidth, read_e, write_e) per memory level
      hw_row  [4]     (comp_scale, eff_mac_pj, leak_rate, noc_bw)
                      with comp_scale = macs / (macs_per_pe * pipeline)
    """
    hw = _hw_numerics(st)
    vals = [*st.stride, *st.dilation]
    zsf = []
    for parent, _, crossed in chain_pairs(sig):
        if crossed:
            vals += _noc_table(st, crossed)
        zs = st.zs_boundary >= 0 and parent >= st.zs_boundary
        zsf += hw["zf"] if zs else (1.0, 1.0, 1.0)
    vals += zsf
    for m in range(len(sig.mem_idx)):
        vals += [hw["mem_bw"][m], hw["e_read"][m], hw["e_write"][m]]
    vals += [hw["macs"] / (hw["macs_per_pe"] * hw["pipeline"]),
             hw["eff_macs"] * hw["mac_energy"], hw["leak_rate"],
             hw["noc_bw"]]
    return np.asarray(vals, np.float32)


def _multi_rows(groups: List[Tuple[HwStatic, np.ndarray, np.ndarray]]):
    """Rows of several single-(arch, workload) groups -> the kernel call's
    host arrays, the mapping axis last, padded (repeating row 0) to the
    power-of-2 `bucket` the jnp path uses, so row counts reuse compiled
    shapes:

      factors [L, 7, Bp] int32    loop bounds per tiling level per dim
      rank    [L, 7, Bp] int32    loop-order position per level per dim
      consts  [C, Bp]    float32  the row's group constants
                                  (`_group_consts`)

    -> (arrays, the kernel's static dict, n rows)."""
    sig = sig_of(groups[0][0])
    n = sum(int(g[1].shape[0]) for g in groups)
    bp = bucket(n)
    factors = np.empty((sig.n_levels, 7, bp), np.int32)
    rank = np.empty((sig.n_levels, 7, bp), np.int32)
    consts = np.empty((sum(const_rows(sig)), bp), np.float32)
    off = 0
    for st, f, r in groups:
        assert sig_of(st) == sig, "kernel groups must share a BatchSig"
        k = f.shape[0]
        factors[..., off:off + k] = np.moveaxis(f, 0, -1)
        rank[..., off:off + k] = np.moveaxis(r, 0, -1)
        consts[:, off:off + k] = _group_consts(st, sig)[:, None]
        off += k
    arrays = [factors, rank, consts]
    for a in arrays:
        a[..., n:] = a[..., :1]
    return arrays, dict(sig=sig), n


def _to_device(arrays, block: int):
    """Host arrays -> device arrays, with the kernel block (at most the
    padded row count)."""
    bp = arrays[0].shape[-1]
    block = min(block, bp)
    assert bp % block == 0, (bp, block)
    return [jnp.asarray(a) for a in arrays], block


def pack_for_kernel_multi(groups: List[Tuple[HwStatic, np.ndarray,
                                             np.ndarray]],
                          block: int = 256):
    """Rows of several single-(arch, workload) groups -> one fused kernel
    batch (`_multi_rows`) on the device.  Every group must share the
    structural `BatchSig` (level layout, tensor set, depthwise) — exactly
    the `evaluate_batch_multi` contract; the numeric hardware/workload
    constants become per-row arrays.  -> (arrays, static, n, block)."""
    arrays, static, n = _multi_rows(groups)
    arrays, block = _to_device(arrays, block)
    return arrays, static, n, block


def pack_for_kernel_arrays(st: HwStatic, factors, rank, block: int = 256):
    """`pack_for_kernel_multi` of one group.  Kept only because the
    benchmark's own tests (tests/bench/test_bench_work.py) import it."""
    return pack_for_kernel_multi([(st, factors, rank)], block)


def mapspace_eval_multi(groups: List[Tuple[HwStatic, np.ndarray,
                                           np.ndarray]], *,
                        block: int = 256, interpret: bool = False):
    """-> (cycles [n], energy [n]) over the concatenated group rows, one
    kernel call for the whole cross-architecture batch.  Each step is a
    span of the ambient tracer: `kernel.pack` (host rows and per-row
    constants), `kernel.transfer` (copy to the device; `bytes`),
    `kernel.launch` (the call up to its return: the enqueue, plus trace,
    lowering and program load on a program's first call in the process,
    counted by `kernel.traces`) and `kernel.fetch` (the wait and the copy
    back)."""
    tr = current_tracer()
    with tr.span("kernel.pack",
                 rows=sum(int(g[1].shape[0]) for g in groups)):
        arrays, static, n = _multi_rows(groups)
    with tr.span("kernel.transfer", rows=n) as sp:
        arrays, block = _to_device(arrays, block)
        sp.set(bytes=sum(int(a.nbytes) for a in arrays))
    with tr.span("kernel.launch", rows=n):
        cycles, energy = mapspace_eval_multi_fwd(*arrays, static=static,
                                                 block=block,
                                                 interpret=interpret)
    with tr.span("kernel.fetch", rows=n):
        return np.asarray(cycles[0, :n]), np.asarray(energy[0, :n])
