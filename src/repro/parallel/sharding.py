"""Logical-axis sharding rules: FSDP x TP x EP x SP on the (pod, data,
model) mesh.

Every model param spec is a tuple of logical axis names (see
models/layers.ParamBuilder); activations are annotated in-model through the
layers.shard hook.  Resolution maps logical -> mesh axes with divisibility
fallback (a dim that does not divide its mesh axis is replicated — recorded
so the roofline can call it out).

Default rules:
  vocab/ff/heads/experts/ssm_inner -> model   (tensor / expert parallel)
  embed                            -> data    (FSDP: params sharded over dp)
  batch                            -> (pod, data)
  kv_seq                           -> data    (decode: shard the KV cache
                                               sequence — flash-decode style)
  seq                              -> data for long-context (SP) else None
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..models import layers as layers_mod

MODEL_AXES = ("vocab", "ff", "heads", "experts", "ssm_inner",
              "ssm_heads")
REPLICATED = ("head_dim", "kv_lora", "q_lora", "layers", "ssm_heads", None)


def _norm_axes(axes):
    """Canonicalize a mesh-axis assignment: a 1-element tuple is the bare
    axis name (PartitionSpec treats ('data',) and 'data' as distinct)."""
    if isinstance(axes, tuple):
        if not axes:
            return None
        if len(axes) == 1:
            return axes[0]
    return axes


@dataclasses.dataclass(frozen=True)
class ShardingRules:
    mesh_axes: Tuple[str, ...]
    fsdp: bool = True                 # shard 'embed' param dim over data
    seq_shard: bool = False           # sequence parallelism for activations
    table: Dict[str, Any] = dataclasses.field(default_factory=dict)

    def resolve(self, logical: Optional[str]):
        if logical in self.table:
            return self.table[logical]
        if logical is None:
            return None
        if logical in MODEL_AXES:
            return "model" if "model" in self.mesh_axes else None
        if logical == "kv_heads":
            return "model" if "model" in self.mesh_axes else None
        if logical == "embed":
            return "data" if (self.fsdp and "data" in self.mesh_axes) \
                else None
        if logical == "batch":
            axes = [a for a in ("pod", "data") if a in self.mesh_axes]
            return _norm_axes(tuple(axes))
        if logical == "moe_cap":
            # expert-capacity dim: data axes (tokens were batch-sharded)
            return [_norm_axes(tuple(a for a in ("pod", "data")
                                     if a in self.mesh_axes))]
        if logical == "kv_seq":
            # candidates tried in order (see spec_to_pspec): the KV seq dim
            # takes whichever axis the batch/head dims left free — this is
            # what makes a replicated-head cache (kv_heads % model != 0)
            # still shard 256-way (flash-decode style seq sharding).
            return [a for a in ("data", "model") if a in self.mesh_axes]
        if logical == "seq":
            # Megatron-style sequence parallelism: block-boundary
            # activations shard their seq dim over 'model' (LN/residual
            # regions), and XLA inserts the all-gather/reduce-scatter pair
            # around attention/MLP.  Long-context SP (seq_shard) prefers
            # the data axes (batch=1 decode/prefill).
            cands = []
            if self.seq_shard:
                axes = _norm_axes(tuple(a for a in ("pod", "data")
                                        if a in self.mesh_axes))
                if axes:
                    cands.append(axes)
            if "model" in self.mesh_axes:
                cands.append("model")
            return cands or None
        return None


def make_rules(mesh: Mesh, *, fsdp: bool = True, seq_shard: bool = False,
               overrides: Optional[Dict[str, Any]] = None) -> ShardingRules:
    return ShardingRules(mesh_axes=tuple(mesh.axis_names), fsdp=fsdp,
                         seq_shard=seq_shard, table=dict(overrides or {}))


def _axis_size(mesh: Mesh, axis) -> int:
    if axis is None:
        return 1
    if isinstance(axis, tuple):
        return int(np.prod([mesh.shape[a] for a in axis]))
    return mesh.shape[axis]


def spec_to_pspec(spec: Tuple, shape: Tuple[int, ...], rules: ShardingRules,
                  mesh: Mesh) -> P:
    """Logical spec + concrete shape -> PartitionSpec with divisibility and
    duplicate-axis fallbacks."""
    used = set()
    out = []
    if shape is not None and len(spec) != len(shape):
        # rank mismatch (e.g. a flattened call site): annotate by trailing
        # alignment, replicating unmatched leading dims.
        spec = ((None,) * max(0, len(shape) - len(spec))
                + tuple(spec)[-len(shape):] if len(shape) else ())
    for i, logical in enumerate(spec):
        axis = rules.resolve(logical)
        candidates = axis if isinstance(axis, list) else [axis]
        chosen = None
        for cand in candidates:
            flat = tuple(cand) if isinstance(cand, tuple) else (cand,)
            if cand is None or any(a in used for a in flat if a):
                continue
            size = _axis_size(mesh, cand)
            if shape is not None and shape[i] % size != 0:
                continue              # non-divisible -> try next candidate
            used.update(a for a in flat if a)
            chosen = cand
            break
        out.append(chosen)
    while out and out[-1] is None:
        out.pop()
    return P(*out)


def param_shardings(specs, params_shapes, rules: ShardingRules, mesh: Mesh):
    """Tree of NamedShardings matching the params tree."""
    def one(spec, shape_leaf):
        shape = shape_leaf.shape if hasattr(shape_leaf, "shape") else None
        return NamedSharding(mesh, spec_to_pspec(tuple(spec), shape, rules,
                                                 mesh))
    return jax.tree_util.tree_map(
        one, specs, params_shapes,
        is_leaf=lambda x: isinstance(x, tuple)
        and all(isinstance(e, (str, type(None))) for e in x))


def batch_pspec(rules: ShardingRules, mesh: Mesh) -> P:
    return P(rules.resolve("batch"))


def install_activation_sharding(mesh: Mesh, rules: ShardingRules):
    """Activate the in-model shard() hook (with_sharding_constraint) and
    the distributed embedding lookup."""
    def fn(x, logical_axes):
        spec = spec_to_pspec(tuple(logical_axes), x.shape, rules, mesh)
        return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))
    layers_mod.set_shard_fn(fn)
    layers_mod.set_embed_lookup(
        lambda table, tokens: masked_embedding_lookup(table, tokens, mesh,
                                                      rules))
    from ..models import moe as moe_mod
    moe_mod.set_moe_ep_impl(
        lambda p, cfg, x: moe_ep_shard_map(p, cfg, x, mesh, rules))


def clear_activation_sharding():
    layers_mod.set_shard_fn(None)
    layers_mod.set_embed_lookup(None)
    from ..models import moe as moe_mod
    moe_mod.set_moe_ep_impl(None)


def moe_ep_shard_map(p, cfg, x, mesh: Mesh, rules: ShardingRules):
    """Explicit expert parallelism: tokens stay (batch x seq)-sharded; each
    device routes + dispatches its local slab into [E, C_loc, d] buffers,
    one all-to-all over 'model' regroups them into [E/ep, C_loc*ep, d]
    slabs matched to the local expert weight shards, and the reverse
    all-to-all brings expert outputs home for the weighted combine.  This
    is the textbook EP dataflow (GShard/Switch) written with shard_map so
    SPMD cannot mis-place the dispatch scatter.  Returns None (caller falls
    back to the global path) when the mesh/shapes don't fit the pattern."""
    e = cfg.n_experts
    if "model" not in mesh.axis_names:
        return None
    ep = mesh.shape["model"]
    b, s, d = x.shape
    if e % ep != 0 or s % ep != 0 or s <= 1:
        return None
    batch_axes = rules.resolve("batch")
    n_dp = _axis_size(mesh, batch_axes)
    if b % n_dp != 0:
        return None
    t_loc = (b // n_dp) * (s // ep)
    cap = int(max(cfg.top_k,
                  (t_loc * cfg.top_k * cfg.capacity_factor) // e))
    from ..models import moe as moe_mod

    has_up = "w_up" in p

    def local(x_loc, router, *ws):
        if has_up:
            wg, wu, wd = ws
        else:
            (wg, wd), wu = ws, None
        bl, sl, _ = x_loc.shape
        xt = x_loc.reshape(bl * sl, d)
        buf, route = moe_mod.moe_local_route_dispatch(xt, router, cfg, cap)
        # [E, C, d] -> [E/ep, C*ep, d]: expert slabs to their owners
        buf = jax.lax.all_to_all(buf, "model", split_axis=0, concat_axis=1,
                                 tiled=True)
        pp = {"w_gate": wg, "w_down": wd}
        if wu is not None:
            pp["w_up"] = wu
        out = moe_mod.expert_ffn(buf, pp, cfg)
        out = jax.lax.all_to_all(out, "model", split_axis=1, concat_axis=0,
                                 tiled=True)
        y = moe_mod.moe_combine(out, route, bl * sl, cfg.top_k, d, cap)
        return y.reshape(bl, sl, d)

    xspec = P(batch_axes, "model", None)
    wspec = P("model", None, None)
    ws = (p["w_gate"], p["w_up"], p["w_down"]) if has_up \
        else (p["w_gate"], p["w_down"])
    in_specs = (xspec, P()) + (wspec,) * len(ws)
    return jax.shard_map(local, mesh=mesh, in_specs=in_specs,
                         out_specs=xspec, check_vma=False)(x, p["router"], *ws)


def masked_embedding_lookup(table, tokens, mesh: Mesh,
                            rules: ShardingRules):
    """Gather from a vocab-sharded table without XLA's replicate-on-gather
    fallback: each model shard gathers its local rows (out-of-range tokens
    clamped + masked to zero) and a psum over 'model' assembles the row.
    Falls back to a plain gather when the vocab doesn't divide the model
    axis (the table is then replicated by the divisibility rule anyway)."""
    vocab = table.shape[0]
    if "model" not in mesh.axis_names or vocab % mesh.shape["model"] != 0:
        return table[tokens]
    tok_spec = spec_to_pspec(("batch",) + (None,) * (tokens.ndim - 1),
                             tokens.shape, rules, mesh)
    tok_spec = P(*(tuple(tok_spec) + (None,) * (tokens.ndim
                                                - len(tok_spec))))
    out_spec = P(*tok_spec, None)

    def local(table_shard, tok):
        shard_rows = table_shard.shape[0]
        lo = jax.lax.axis_index("model") * shard_rows
        idx = tok - lo
        ok = (idx >= 0) & (idx < shard_rows)
        vals = table_shard[jnp.clip(idx, 0, shard_rows - 1)]
        vals = jnp.where(ok[..., None], vals, 0)
        return jax.lax.psum(vals, "model")

    return jax.shard_map(local, mesh=mesh,
                         in_specs=(P("model", None), tok_spec),
                         out_specs=out_spec, check_vma=False)(table, tokens)
