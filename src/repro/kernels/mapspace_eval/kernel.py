"""TRIM mapspace scoring as a Pallas TPU kernel — the paper's hot loop.

A mapspace is a batch of mappings; scoring one mapping is ~1k scalar ops
(innermost-relevant-loop scans, masked products, traffic/energy sums), so a
Timeloop-style Python loop is interpreter-bound.  Here the mapping axis is
the lane axis: every per-mapping tensor is stored feature-major, [F, B],
a block of mappings is a [F, BLOCK] tile in VMEM, and the whole scoring
pipeline is VPU vector arithmetic on [S, BLOCK] slot tiles and [1, BLOCK]
rows (chain levels and tensors statically unrolled).  Slot reductions run
over sublanes, and the outputs are lane-dense [1, BLOCK] rows.

Semantics: identical to core.batch_eval restricted to no-bypass mappings
(storage chain = all memory levels) — including input halo credit, psum
read-modify-write, NoC classification, and zero-skip energy discounts.
The host sends the packed rows, factors and rank [L, 7, B], and one
array of per-row constants [C, B] (`const_rows`).  A jnp prologue in the
same jitted program derives the kernel's per-mapping tensors from them,
row k of each tensor being feature k of every mapping:

  bounds/cum [S,B]     slot loop bounds (nest order) and their cumprod
  rel_{i,w,o} [S,B]    relevance masks per tensor
  tw_u/tw_p [L1*3,B]   union / per-instance tile words per chain pair
                       (row j*3+t: pair j, tensor t)
  fresh [L1,S,B]       input fresh-words if the innermost relevant slot is
                       this slot (== tw_u for non-sliding dims => the
                       sliding formula is uniform)
  ia/ib [L1,B]         parent/child used-instance counts per pair
  noc_e [L1*3,B]       NoC pJ/word per pair per tensor (0 if no crossing)
  noc_m [L1,B]         1 if the pair crosses a routing level

The hardware/workload constants are per-row arrays too (zsf [L1*3, B],
mem_par [Lm*3, B], hw_row [4, B], sliced from the constants), so rows of
any architectures sharing a structural BatchSig fuse into one call — the
`evaluate_batch_multi` contract; a single mapspace is a one-group call.
The call is a module-level `jax.jit` keyed on the `BatchSig`, `block`
and the bucketed shapes, so each program is traced and lowered once per
process.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from ...core.batch_eval import RELEVANT, SLIDING, BatchSig
from ...core.workload import N_, M_, C_, R_, S_, E_, F_
from ...obs import current_tracer


def _row(ref, k: int):
    """Feature row k of a [F, Bm] ref as a [1, Bm] vector."""
    return ref[k:k + 1, :]


def _score_kernel_multi(bounds_ref, cum_ref, rel_i_ref, rel_w_ref,
                        rel_o_ref, tw_u_ref, tw_p_ref, fresh_ref, ia_ref,
                        ib_ref, noc_e_ref, noc_m_ref, zsf_ref, mem_par_ref,
                        hw_row_ref, cycles_ref, energy_ref, *,
                        vis: Tuple[int, ...], n_mem: int):
    """The scoring pipeline over one [*, Bm] block of mappings, with
    per-row hardware/workload constants (same contract as
    `core.batch_eval.evaluate_batch_multi`):

      zsf     [L1*3, Bm]  zero-skip factor per chain pair per tensor
      mem_par [Lm*3, Bm]  (bandwidth, read_e, write_e) per memory level
      hw_row  [4, Bm]     (comp_scale, eff_mac_pj, leak_rate, noc_bw)
                          with comp_scale = macs / (macs_per_pe * pipe)
    """
    bounds = bounds_ref[...]                    # [S, Bm]
    cum = cum_ref[...]
    rel_refs = (rel_i_ref, rel_w_ref, rel_o_ref)
    rel = [r[...] for r in rel_refs]
    n_slots, bm = bounds.shape
    # Mosaic's iota is integer-only: build 1-based slot positions in int32
    pos = (jax.lax.broadcasted_iota(jnp.int32, bounds.shape, 0) + 1
           ).astype(jnp.float32)
    active = jnp.where(bounds > 1.0, 1.0, 0.0)

    zero = jnp.zeros((1, bm), jnp.float32)
    reads = [zero] * n_mem
    writes = [zero] * n_mem
    raw = [zero] * n_mem
    noc_words = zero
    dyn = _row(hw_row_ref, 1)

    L1 = len(vis)
    for j in range(L1):
        v = float(vis[j])
        i_a = _row(ia_ref, j)
        i_b = _row(ib_ref, j)
        nm = _row(noc_m_ref, j)
        visible = jnp.where(pos <= v, 1.0, 0.0)
        is_term = j == L1 - 1
        for t in range(3):
            tw_u = _row(tw_u_ref, j * 3 + t)
            tw_p = _row(tw_p_ref, j * 3 + t)
            r = visible * rel[t] * active
            k1 = jnp.max(r * pos, axis=0, keepdims=True)    # [1,Bm] 1-based
            has = k1 > 0.5
            oh = jnp.where(pos == jnp.maximum(k1, 1.0), 1.0, 0.0)
            p_k = jnp.where(has, jnp.sum(cum * oh, axis=0, keepdims=True),
                            1.0)
            b_k = jnp.where(has, jnp.sum(bounds * oh, axis=0,
                                         keepdims=True), 1.0)
            vv = p_k
            outer = p_k / b_k
            zsf = _row(zsf_ref, j * 3 + t)
            ne = _row(noc_e_ref, j * 3 + t)
            if t == 2:                                        # output
                # product of the relevant active bounds at or inside k1,
                # unrolled over the visible slots (Mosaic has no
                # reduce_prod); slots past `v` are never visible
                dd = jnp.ones((1, bm), jnp.float32)
                for s in range(min(int(v), n_slots)):
                    b_s = _row(bounds_ref, s)
                    take = ((_row(rel_refs[t], s) > 0) & (b_s > 1.0)
                            & (float(s + 1) <= k1))
                    dd = dd * jnp.where(take, b_s, 1.0)
                dd = jnp.where(has, dd, 1.0)
                p_rd = i_a * (vv - dd) * tw_u
                p_wr = i_a * vv * tw_u
                reads[j] += p_rd * zsf
                writes[j] += p_wr * zsf
                raw[j] += p_rd + p_wr
                if not is_term:
                    c_rd = i_b * vv * tw_p
                    c_wr = i_b * (vv - dd) * tw_p
                    reads[j + 1] += c_rd * zsf
                    writes[j + 1] += c_wr * zsf
                    raw[j + 1] += c_rd + c_wr
                nw = i_b * (2 * vv - dd) * tw_p * nm
                noc_words += nw
                dyn += nw * zsf * ne
            else:
                if t == 0:                                    # input: halo
                    fr = jnp.sum(fresh_ref[j] * oh, axis=0, keepdims=True)
                    words = outer * (tw_u + (b_k - 1.0) * fr)
                    words = jnp.where(has, words, tw_u)
                else:
                    words = jnp.where(has, vv * tw_u, tw_u)
                p_rd = i_a * words
                reads[j] += p_rd * zsf
                raw[j] += p_rd
                if not is_term:
                    c_wr = i_b * vv * tw_p
                    writes[j + 1] += c_wr * zsf
                    raw[j + 1] += c_wr
                nw = p_rd * nm
                noc_words += nw
                dyn += nw * zsf * ne

    pes = _row(ib_ref, L1 - 1)                  # instances at compute leaf
    cycles = _row(hw_row_ref, 0) / jnp.maximum(pes, 1.0)
    for m in range(n_mem):
        inst_m = _row(ia_ref, m)                # parent of pair m = level m
        cycles = jnp.maximum(cycles,
                             raw[m] / (_row(mem_par_ref, m * 3) * inst_m))
        dyn += (reads[m] * _row(mem_par_ref, m * 3 + 1)
                + writes[m] * _row(mem_par_ref, m * 3 + 2))
    cycles = jnp.maximum(cycles, noc_words / _row(hw_row_ref, 3))
    energy = dyn + _row(hw_row_ref, 2) * cycles
    cycles_ref[...] = cycles
    energy_ref[...] = energy


# ---------------------------------------------------------------------------
# The prologue: the kernel's per-mapping tensors from the packed rows, in
# the same jitted program as the kernel.  Every product that can pass 2^24
# is written out left to right (no cumprod/prod, whose reduction order XLA
# may change), so the tensors equal bit for bit the NumPy float32
# derivation that tests/test_kernel_prologue.py keeps as their oracle.
# ---------------------------------------------------------------------------
def chain_pairs(sig: BatchSig) -> Tuple[Tuple[int, int, Tuple[int, ...]],
                                        ...]:
    """Per chain pair j (memory level j -> memory level j + 1, the last
    pair -> compute): (parent tiling index, child tiling index, the
    routing levels between them)."""
    mem = sig.mem_idx
    pairs = []
    for j, parent in enumerate(mem):
        child = mem[j + 1] if j + 1 < len(mem) else sig.n_levels
        pairs.append((parent, child, tuple(r for r in sig.rout_idx
                                           if parent < r < child)))
    return tuple(pairs)


def const_rows(sig: BatchSig) -> Tuple[int, int, int, int, int]:
    """Row counts of the per-row constants array, in its order: geometry
    (u, v, dr, ds), NoC energy tables (per pair that crosses k routing
    levels, per tensor, 2^k entries), zsf, mem_par, hw_row."""
    n_mem = len(sig.mem_idx)
    n_noc = sum(3 * 2 ** len(c) for _, _, c in chain_pairs(sig) if c)
    return 4, n_noc, n_mem * 3, n_mem * 3, 4


def _halo(t, geo):
    n, m, c, r, s, e, f = t
    u, v, dr, ds = geo
    return (e - 1.0) * u + (r - 1.0) * dr + 1.0, \
        (f - 1.0) * v + (s - 1.0) * ds + 1.0


def _tile_words(sig: BatchSig, t, geo):
    """Seven [B] tile extents -> three [B] word counts in TENSORS order
    (`batch_eval.tile_words_np`, its products in its order)."""
    n, m, c, r, s, e, f = t
    p, q = _halo(t, geo)
    w = r * s * c * m if sig.has_weight else jnp.zeros_like(n)
    o = n * e * f * (c if sig.depthwise else m)
    return [n * c * p * q, w, o]


def _fresh(t, geo, d: int):
    """Input words new to a step of sliding dim `d` over tile `t`."""
    n, m, c, r, s, e, f = t
    u, v, dr, ds = geo
    p, q = _halo(t, geo)
    if d == E_:
        return n * c * jnp.minimum(p, e * u) * q
    if d == F_:
        return n * c * p * jnp.minimum(q, f * v)
    if d == R_:
        return n * c * jnp.minimum(p, r * dr) * q
    return n * c * p * jnp.minimum(q, s * ds)


# per tensor, the dims whose split at a routing level makes its NoC
# traffic multicast (input: M; weight: N, E, F) or accumulate (output: C,
# R, S) instead of unicast
_NOC_SPLIT = ((M_,), (N_, E_, F_), (C_, R_, S_))


def _splits(level, dims):
    """[B] bool: some dim of `dims` has a factor above 1 at `level`."""
    return functools.reduce(jnp.logical_or, [level[d] > 1.0 for d in dims])


def _kernel_inputs(factors, rank, consts, *, sig: BatchSig):
    """The kernel's fifteen inputs (module docstring) from the packed rows
    factors/rank [L, 7, B] and the per-row constants consts [C, B]."""
    L, n_mem = sig.n_levels, len(sig.mem_idx)
    f = factors.astype(jnp.float32)
    fl = [[f[l, d] for d in range(7)] for l in range(L)]     # [B] each
    one = jnp.ones_like(fl[0][0])
    zero = jnp.zeros_like(one)
    n_geo, n_noc, n_zsf, n_mp, _ = const_rows(sig)
    geo = [consts[i] for i in range(n_geo)]
    noc_tab = [consts[n_geo + i] for i in range(n_noc)]
    at = n_geo + n_noc
    zsf = consts[at:at + n_zsf]
    mem_par = consts[at + n_zsf:at + n_zsf + n_mp]
    hw_row = consts[at + n_zsf + n_mp:]

    # tile_at[l]: the level-l tile, multiplied in from the innermost level
    tile_at = [None] * (L - 1) + [fl[L - 1], [one] * 7]
    for l in range(L - 2, -1, -1):
        tile_at[l] = [tile_at[l + 1][d] * fl[l][d] for d in range(7)]

    # each memory level's seven loop slots in nest order: bound and dim
    pos = jnp.arange(7, dtype=rank.dtype)[:, None]
    bounds, slot_dim = [], []
    for li in sig.mem_idx:
        sb = jnp.ones((7,) + one.shape, jnp.float32)
        sd = jnp.zeros((7,) + one.shape, jnp.int32)
        for d in range(7):
            here = rank[li, d][None, :] == pos
            sb = jnp.where(here, f[li, d][None, :], sb)
            sd = jnp.where(here, d, sd)
        bounds.append(sb)
        slot_dim.append(sd)
    bounds = jnp.concatenate(bounds)
    slot_dim = jnp.concatenate(slot_dim)
    cum = [bounds[0]]
    for s in range(1, n_mem * 7):
        cum.append(cum[-1] * bounds[s])
    cum = jnp.stack(cum)

    def relevance(mask):
        hit = jnp.zeros(slot_dim.shape, bool)
        for d in range(7):
            if mask[d]:
                hit = hit | (slot_dim == d)
        return hit.astype(jnp.float32)

    rel_out = (np.array([1, 1, 1, 0, 0, 1, 1], bool) if sig.depthwise
               else RELEVANT["output"])
    rel = [relevance(RELEVANT["input"]), relevance(RELEVANT["weight"]),
           relevance(rel_out)]

    rout_prod = {}
    for r in sig.rout_idx:
        p = fl[r][0]
        for d in range(1, 7):
            p = p * fl[r][d]
        rout_prod[r] = p

    def inst_before(tiling_idx):
        inst = one
        for r in sig.rout_idx:
            if r < tiling_idx:
                inst = inst * rout_prod[r]
        return inst

    tw_u, tw_p, fresh, ia, ib, noc_e, noc_m = [], [], [], [], [], [], []
    k = 0
    for parent, child, crossed in chain_pairs(sig):
        per = tile_at[child]
        sp = [one] * 7
        for r in crossed:
            sp = [sp[d] * fl[r][d] for d in range(7)]
        union = [per[d] * sp[d] for d in range(7)]
        words = _tile_words(sig, union, geo)
        tw_u += words
        tw_p += _tile_words(sig, per, geo)
        ia.append(inst_before(parent))
        ib.append(inst_before(child))
        fr = jnp.broadcast_to(words[0], slot_dim.shape)
        for d in range(7):
            if SLIDING[d]:
                fr = jnp.where(slot_dim == d, _fresh(union, geo, d)[None],
                               fr)
        fresh.append(fr)
        noc_m.append(one if crossed else zero)
        if not crossed:
            noc_e += [zero] * 3
            continue
        # the entry of each tensor's table: bit i set where crossed level
        # i multicasts (input, weight) or accumulates (output)
        for dims in _NOC_SPLIT:
            idx = sum(jnp.where(_splits(fl[r], dims), 1 << i, 0)
                      for i, r in enumerate(crossed))
            val = noc_tab[k]
            for c in range(1, 2 ** len(crossed)):
                val = jnp.where(idx == c, noc_tab[k + c], val)
            noc_e.append(val)
            k += 2 ** len(crossed)

    return (bounds, cum, *rel, jnp.stack(tw_u), jnp.stack(tw_p),
            jnp.stack(fresh), jnp.stack(ia), jnp.stack(ib),
            jnp.stack(noc_e), jnp.stack(noc_m), zsf, mem_par, hw_row)


def _score(*tensors, n_mem: int, block: int, interpret: bool):
    """The Pallas call over the lane (mapping) axis: every input is
    [..., B] and is tiled [..., block]; outputs are (cycles, energy) as
    [1, B]."""
    b = tensors[0].shape[-1]
    assert b % block == 0, (b, block)

    def spec(a):
        lead = a.shape[:-1]
        return pl.BlockSpec(lead + (block,),
                            lambda i, _n=len(lead): (0,) * _n + (i,))

    out_spec = pl.BlockSpec((1, block), lambda i: (0, i))
    out_shape = jax.ShapeDtypeStruct((1, b), jnp.float32)
    vis = tuple((j + 1) * 7 for j in range(n_mem))
    return pl.pallas_call(
        functools.partial(_score_kernel_multi, vis=vis, n_mem=n_mem),
        grid=(b // block,),
        in_specs=[spec(a) for a in tensors],
        out_specs=[out_spec, out_spec],
        out_shape=[out_shape, out_shape],
        interpret=interpret,
        name="mapspace_eval_multi",
    )(*tensors)


@functools.partial(jax.jit, static_argnames=("sig", "block", "interpret"))
def _multi_call(factors, rank, consts, *, sig: BatchSig, block: int,
                interpret: bool):
    """The prologue and the Pallas call as one program.  Compiled once per
    process for each `BatchSig`, `block`, `interpret` and row bucket.
    This body runs only while JAX traces it, so `kernel.traces` counts
    the programs built, not the calls."""
    current_tracer().metrics.counter("kernel.traces").inc()
    return _score(*_kernel_inputs(factors, rank, consts, sig=sig),
                  n_mem=len(sig.mem_idx), block=block, interpret=interpret)


def mapspace_eval_multi_fwd(factors, rank, consts, *, static: dict,
                            block: int = 256, interpret: bool = False):
    """Multi-architecture forward: factors/rank [L, 7, B] int32 and the
    per-row constants consts [C, B] float32 (`ops.pack_for_kernel_multi`),
    B a multiple of `block`.  Returns (cycles [1, B], energy [1, B])."""
    return _multi_call(factors, rank, consts, sig=static["sig"],
                       block=block, interpret=interpret)
