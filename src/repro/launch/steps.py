"""Jitted step builders (shared by dryrun / train / serve).

Each builder returns (jit_fn, arg_shape_structs) with in/out shardings
resolved from the logical rules, ready for .lower(...).compile() (dry-run)
or execution (real run).
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..configs.base import ModelConfig
from ..configs.shapes import ShapeSpec
from ..models import decode_step, forward, init_model
from ..models.model import cache_specs
from ..parallel.sharding import (ShardingRules, clear_activation_sharding,
                                 install_activation_sharding,
                                 param_shardings, spec_to_pspec)
from ..train.optimizer import OptConfig, init_opt_state
from ..train.train_step import TrainConfig, TrainState, make_train_step
from .specs import batch_logical_specs, decode_specs, input_specs


@contextlib.contextmanager
def _activation_sharding(mesh, rules):
    """The model's sharding hooks, installed while a step is traced and
    cleared after, so they never leak into a later trace."""
    install_activation_sharding(mesh, rules)
    try:
        yield
    finally:
        clear_activation_sharding()


def _repl(mesh):
    return NamedSharding(mesh, P())


def _leaf_sharding(mesh, rules, spec, shape_struct):
    return NamedSharding(mesh, spec_to_pspec(tuple(spec),
                                             shape_struct.shape, rules,
                                             mesh))


def model_shapes(cfg: ModelConfig):
    """(params ShapeDtypeStructs, logical-axis specs) — no allocation.
    The spec tree (strings) is captured via a side channel because
    eval_shape only admits array outputs."""
    box = {}

    def f(k):
        p, s = init_model(cfg, k)
        box["specs"] = s
        return p

    params_shapes = jax.eval_shape(f, jax.random.PRNGKey(0))
    return params_shapes, box["specs"]


def build_train_step(cfg: ModelConfig, mesh: Mesh, rules: ShardingRules,
                     spec: ShapeSpec, *, opt_cfg: Optional[OptConfig] = None,
                     tc: Optional[TrainConfig] = None):
    opt_cfg = opt_cfg or OptConfig()
    tc = tc or TrainConfig()
    params_shapes, specs = model_shapes(cfg)
    p_sh = param_shardings(specs, params_shapes, rules, mesh)
    opt_shapes = jax.eval_shape(
        lambda p: init_opt_state(opt_cfg, p), params_shapes)
    # m/v/master share the param tree structure; additionally ZeRO-shard
    # any still-replicated dim over the data axis (fp32 optimizer state is
    # the largest consumer — expert weights are E-sharded only).
    def zero_extend(sh, leaf):
        spec = list(sh.spec) + [None] * (len(leaf.shape) - len(sh.spec))
        used = {a for s_ in spec if s_ for a in
                (s_ if isinstance(s_, tuple) else (s_,))}
        if "data" in mesh.axis_names and "data" not in used:
            dsz = mesh.shape["data"]
            for i, s_ in enumerate(spec):
                if s_ is None and leaf.shape[i] % dsz == 0 \
                        and leaf.shape[i] >= dsz:
                    spec[i] = "data"
                    break
        return NamedSharding(mesh, P(*spec))

    opt_p_sh = jax.tree_util.tree_map(zero_extend, p_sh, params_shapes)
    from ..train.optimizer import OptState
    opt_sh = OptState(step=_repl(mesh), m=opt_p_sh, v=opt_p_sh,
                      master=opt_p_sh if opt_cfg.master_fp32 else None)
    state_shapes = TrainState(params_shapes, opt_shapes, None)
    state_sh = TrainState(p_sh, opt_sh, None)

    in_specs = input_specs(cfg, spec)
    blog = batch_logical_specs(cfg)
    b_sh = {k: _leaf_sharding(mesh, rules, blog[k], v)
            for k, v in in_specs.items()}

    step = make_train_step(cfg, opt_cfg, tc)

    def wrapped(state, batch):
        with _activation_sharding(mesh, rules):
            return step(state, batch)

    metrics_sh = {"loss": _repl(mesh), "grad_norm": _repl(mesh),
                  "lr": _repl(mesh)}
    jit_fn = jax.jit(wrapped,
                     in_shardings=(state_sh, b_sh),
                     out_shardings=(state_sh, metrics_sh),
                     donate_argnums=(0,))
    return jit_fn, (state_shapes, in_specs), (state_sh, b_sh)


def build_prefill_step(cfg: ModelConfig, mesh: Mesh, rules: ShardingRules,
                       spec: ShapeSpec, *, remat: str = "none"):
    params_shapes, specs = model_shapes(cfg)
    p_sh = param_shardings(specs, params_shapes, rules, mesh)
    in_specs = input_specs(cfg, spec)
    blog = batch_logical_specs(cfg)
    b_sh = {k: _leaf_sharding(mesh, rules, blog[k], v)
            for k, v in in_specs.items()}

    def prefill(params, batch):
        with _activation_sharding(mesh, rules):
            return forward(params, cfg, batch, remat=remat,
                           logits_mode="last")

    jit_fn = jax.jit(prefill, in_shardings=(p_sh, b_sh))
    return jit_fn, (params_shapes, in_specs), (p_sh, b_sh)


def build_decode_step(cfg: ModelConfig, mesh: Mesh, rules: ShardingRules,
                      spec: ShapeSpec, *, mla_absorb: bool = False):
    params_shapes, specs = model_shapes(cfg)
    p_sh = param_shardings(specs, params_shapes, rules, mesh)
    cache_shapes, token_spec = decode_specs(cfg, spec)
    cspecs = cache_specs(cfg)
    c_sh = jax.tree_util.tree_map(
        lambda sp, shp: _leaf_sharding(mesh, rules, sp, shp),
        cspecs, cache_shapes,
        is_leaf=lambda x: isinstance(x, tuple)
        and all(isinstance(e, (str, type(None))) for e in x))
    t_sh = _leaf_sharding(mesh, rules, ("batch",), token_spec)

    def serve_step(params, cache, token, pos):
        with _activation_sharding(mesh, rules):
            return decode_step(params, cfg, cache, token, pos,
                               mla_absorb=mla_absorb)

    jit_fn = jax.jit(serve_step,
                     in_shardings=(p_sh, c_sh, t_sh, _repl(mesh)),
                     out_shardings=(None, c_sh),
                     donate_argnums=(1,))
    pos_spec = jax.ShapeDtypeStruct((), jnp.int32)
    return jit_fn, (params_shapes, cache_shapes, token_spec, pos_spec), \
        (p_sh, c_sh, t_sh)


