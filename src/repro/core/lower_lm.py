"""Lower the assigned LM architectures to TRIM intra-layer workloads.

Every transformer/SSM layer op whose primary computation is a (batched)
matmul maps onto the paper's 7-dim loop nest (paper §3.2: "matrix-matrix
multiplications can be defined by setting R, S, E, F equal to 1").  This
extends TRIM's task analyst beyond CONV/POOL/FC to the modern-architecture
pool — the DSE and the TPU sharding planner (tpu_adapter) both consume it.

For training shapes each matmul also emits BW/WG workloads (transposed
operand roles, same MAC count) — the paper's FC-layer treatment.

A model lowers to one block per kind of layer, each with its layer
count, in schedule order (an MoE model's leading dense layers first),
and the unrepeated head.  A chip's share of the experts
(`ModelConfig.experts_held`) lowers each held expert as its own GEMM.
"""
from __future__ import annotations

import dataclasses
from typing import List, Tuple

from ..configs.base import ModelConfig
from ..configs.shapes import ShapeSpec
from .explorer import _workload_key
from .workload import Workload, matmul_workload


@dataclasses.dataclass
class LoweredLM:
    # (one block's workloads, its layer count) per kind, schedule order
    kinds: List[Tuple[List[Workload], int]]
    tail: List[Workload]                 # unrepeated (lm head, ...)

    @property
    def n_layers(self) -> int:
        return sum(n for _, n in self.kinds)

    def block_workloads(self) -> List[Workload]:
        """The distinct workloads of every kind's block, in order."""
        return list(dict.fromkeys(w for wls, _ in self.kinds for w in wls))

    def all_workloads(self) -> List[Workload]:
        out: List[Workload] = []
        for wls, n in self.kinds:
            out += list(wls) * n
        return out + list(self.tail)

    def total_macs(self) -> int:
        return sum(sum(w.macs for w in wls) * n for wls, n in self.kinds) \
            + sum(w.macs for w in self.tail)


def _mm(name, rows, cols, inner, phase="FW"):
    return matmul_workload(rows=int(rows), cols=int(cols), inner=int(inner),
                           name=name, phase=phase)


def _with_training(wls: List[Workload], training: bool) -> List[Workload]:
    if not training:
        return wls
    out = list(wls)
    for w in wls:
        n, m, c = w.dims[0], w.dims[1], w.dims[2]
        out.append(_mm(w.name + ".BW", n, c, m, phase="BW"))
        out.append(_mm(w.name + ".WG", c, m, n, phase="WG"))
    return out


def _attention(cfg: ModelConfig, b: int, s: int, decode: bool
               ) -> List[Workload]:
    """Attention of one layer over `b` sequences of `s` positions (one new
    position each at decode); causal at train and prefill, so the score
    and attention-value rows are halved there."""
    sq = 1 if decode else s              # query length
    t = b * sq
    d = cfg.d_model
    wls: List[Workload] = []
    if cfg.attn == "mla":
        r = cfg.kv_lora_rank
        qk = cfg.qk_nope_dim + cfg.qk_rope_dim
        if cfg.q_lora_rank:
            wls.append(_mm("q_a", t, cfg.q_lora_rank, d))
            wls.append(_mm("q_b", t, cfg.n_heads * qk, cfg.q_lora_rank))
        else:
            wls.append(_mm("q", t, cfg.n_heads * qk, d))
        wls.append(_mm("kv_a", t, r + cfg.qk_rope_dim, d))
        kv_len = s
        wls.append(_mm("k_expand", (b * kv_len if not decode else t),
                       cfg.n_heads * cfg.qk_nope_dim, r))
        wls.append(_mm("v_expand", (b * kv_len if not decode else t),
                       cfg.n_heads * cfg.v_head_dim, r))
        causal_frac = 1.0 if decode else 0.5
        att = int(b * cfg.n_heads * sq * causal_frac)
        wls.append(_mm("scores", att, kv_len, qk))
        wls.append(_mm("attn_v", att, cfg.v_head_dim, kv_len))
        wls.append(_mm("o", t, d, cfg.n_heads * cfg.v_head_dim))
    elif cfg.attn == "gqa" and cfg.n_heads:
        hd = cfg.d_head
        wls.append(_mm("q", t, cfg.n_heads * hd, d))
        wls.append(_mm("k", t, cfg.n_kv_heads * hd, d))
        wls.append(_mm("v", t, cfg.n_kv_heads * hd, d))
        kv_len = s
        eff = min(kv_len, cfg.sliding_window) if (cfg.sliding_window and
                                                  decode) else kv_len
        causal_frac = 0.5 if (not decode and cfg.sliding_window == 0) else 1.0
        wls.append(_mm("scores", int(b * cfg.n_heads * sq * causal_frac),
                       eff, hd))
        wls.append(_mm("attn_v", int(b * cfg.n_heads * sq * causal_frac),
                       hd, eff))
        wls.append(_mm("o", t, d, cfg.n_heads * hd))
    return wls


def _ssm(cfg: ModelConfig, b: int, s: int, decode: bool) -> List[Workload]:
    t = b * (1 if decode else s)
    di = cfg.d_inner
    g, n = cfg.ssm_ngroups, cfg.d_state
    nh, p = cfg.n_ssm_heads, cfg.ssm_headdim
    wls = [_mm("ssm_in", t, 2 * di + 2 * g * n + nh, cfg.d_model)]
    if decode:
        wls.append(_mm("ssm_state", b * nh, n, p))
        wls.append(_mm("ssm_out_state", b * nh, p, n))
    else:
        q = cfg.chunk
        nc = max(s // q, 1)
        wls.append(_mm("ssd_scores", b * nc * nh * q, q, n))
        wls.append(_mm("ssd_diag", b * nc * nh * q, p, q))
        wls.append(_mm("ssd_states", b * nc * nh * n, p, q))
        wls.append(_mm("ssd_off", b * nc * nh * q, p, n))
    wls.append(_mm("ssm_out", t, cfg.d_model, di))
    return wls


def _gate_width(cfg: ModelConfig, width: int) -> int:
    """Width of the up projection: gate and up fused under SwiGLU."""
    return width * (2 if cfg.act == "swiglu" else 1)


def _mlp(cfg: ModelConfig, t: int, d_ff: int) -> List[Workload]:
    return [_mm("mlp_up", t, _gate_width(cfg, d_ff), cfg.d_model),
            _mm("mlp_down", t, cfg.d_model, d_ff)]


def _experts(cfg: ModelConfig, t: int) -> List[Workload]:
    """The router over all `n_experts`, then each expert held here as its
    own GEMM pair, then the shared experts fused (every token goes
    through each of them).  Under expert parallelism over
    `n_experts / held` chips an expert receives `t * top_k *
    capacity_factor / held` of the tokens, rounded up."""
    e, k, f, d = cfg.n_experts, cfg.top_k, cfg.d_expert, cfg.d_model
    held = cfg.experts_held or e
    if not 0 < held <= e or e % held:
        raise ValueError(f"{cfg.name}: experts_held {cfg.experts_held} "
                         f"does not divide n_experts {e}")
    rows = -(-int(t * k * cfg.capacity_factor) // held)
    wls = [_mm("router", t, e, d)]
    for i in range(held):
        wls.append(_mm(f"expert_up.e{i}", rows, _gate_width(cfg, f), d))
        wls.append(_mm(f"expert_down.e{i}", rows, d, f))
    if cfg.n_shared_experts:
        fs = f * cfg.n_shared_experts
        wls.append(_mm("shared_up", t, _gate_width(cfg, fs), d))
        wls.append(_mm("shared_down", t, d, fs))
    return wls


def lower_block(cfg: ModelConfig, spec: ShapeSpec) -> LoweredLM:
    """Workloads of each kind of block, with its layer count, and the tail
    (head).  An MoE model's `first_dense_layers` are a kind of their own
    with an MLP of width `d_ff_dense`.  Records a `lower` span into the
    ambient `repro.obs` tracer."""
    from ..obs import current_tracer
    with current_tracer().span("lower", model=cfg.name,
                               shape=spec.name) as sp:
        b, s = spec.global_batch, spec.seq_len
        training = spec.kind == "train"
        decode = spec.kind == "decode"
        t = b * (1 if decode else s)     # tokens processed this step
        mixer = _attention(cfg, b, s, decode)
        if cfg.family in ("ssm", "hybrid"):
            mixer += _ssm(cfg, b, s, decode)
        if cfg.family == "moe":
            dense = cfg.first_dense_layers
            blocks = [(mixer + _mlp(cfg, t, cfg.d_ff_dense or cfg.d_ff),
                       dense),
                      (mixer + _experts(cfg, t), cfg.n_layers - dense)]
        elif cfg.d_ff:
            blocks = [(mixer + _mlp(cfg, t, cfg.d_ff), cfg.n_layers)]
        else:
            blocks = [(mixer, cfg.n_layers)]
        lowered = LoweredLM(
            kinds=[(_with_training(wls, training), n)
                   for wls, n in blocks if n > 0],
            tail=_with_training([_mm("lm_head", t, cfg.vocab, cfg.d_model)],
                                training))
        every = lowered.all_workloads()
        sp.set(kinds=len(lowered.kinds), layers=lowered.n_layers,
               workloads=len(every),
               shapes=len({_workload_key(w) for w in every}),
               expert_gemms=sum(w.layer in ("expert_up", "expert_down")
                                for w in every))
    return lowered
