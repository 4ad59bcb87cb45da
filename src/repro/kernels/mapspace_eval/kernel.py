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
The ops wrapper precomputes per mapping (cheap numpy), row k of each
tensor being feature k of every mapping:

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
mem_par [Lm*3, B], hw_row [4, B]), so rows of any architectures sharing a
structural BatchSig fuse into one call — the `evaluate_batch_multi`
contract; a single mapspace is a one-group call.  The call is a
module-level `jax.jit` keyed on the chain layout (`vis`, `n_mem`),
`block` and the bucketed shapes, so each program is traced and lowered
once per process.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

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


@functools.partial(jax.jit,
                   static_argnames=("vis", "n_mem", "block", "interpret"))
def _multi_call(*arrays, vis: Tuple[int, ...], n_mem: int, block: int,
                interpret: bool):
    """The Pallas call over the lane (mapping) axis: every input is
    [..., B] and is tiled [..., block]; outputs are (cycles, energy) as
    [1, B].  Compiled once per process for each chain layout (`vis`,
    `n_mem`), `block`, `interpret` and array shapes.  This body runs only
    while JAX traces it, so `kernel.traces` counts the programs built, not
    the calls."""
    current_tracer().metrics.counter("kernel.traces").inc()
    b = arrays[0].shape[-1]
    assert b % block == 0, (b, block)

    def spec(a):
        lead = a.shape[:-1]
        return pl.BlockSpec(lead + (block,),
                            lambda i, _n=len(lead): (0,) * _n + (i,))

    out_spec = pl.BlockSpec((1, block), lambda i: (0, i))
    out_shape = jax.ShapeDtypeStruct((1, b), jnp.float32)
    return pl.pallas_call(
        functools.partial(_score_kernel_multi, vis=vis, n_mem=n_mem),
        grid=(b // block,),
        in_specs=[spec(a) for a in arrays],
        out_specs=[out_spec, out_spec],
        out_shape=[out_shape, out_shape],
        interpret=interpret,
        name="mapspace_eval_multi",
    )(*arrays)


def mapspace_eval_multi_fwd(bounds, cum, rel_i, rel_w, rel_o, tw_u, tw_p,
                            fresh, ia, ib, noc_e, noc_m, zsf, mem_par,
                            hw_row, *, static: dict, block: int = 256,
                            interpret: bool = False):
    """Multi-architecture forward: the twelve per-mapping tensors plus
    per-row hardware arrays (zsf [L1*3, B], mem_par [Lm*3, B],
    hw_row [4, B]).  All array args share the trailing mapping axis B
    (a multiple of `block`).  Returns (cycles [1, B], energy [1, B])."""
    return _multi_call(bounds, cum, rel_i, rel_w, rel_o, tw_u, tw_p, fresh,
                       ia, ib, noc_e, noc_m, zsf, mem_par, hw_row,
                       vis=static["vis"], n_mem=static["n_mem"],
                       block=block, interpret=interpret)
