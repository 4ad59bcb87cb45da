"""Tests for the LM lowering (core/lower_lm) and the TPU sharding planner
(core/tpu_adapter)."""
import collections
import dataclasses
import hashlib
import math

import pytest

from repro.configs import SHAPES, get_config
from repro.configs.registry import ARCHS
from repro.configs.shapes import ShapeSpec, is_skipped
from repro.core.lower_lm import lower_block
from repro.core.tpu_adapter import (_factor_clip, make_tpu_pod_desc,
                                    plan_cell, plan_workload,
                                    trim_sharding_overrides)
from repro.core.workload import matmul_workload


def test_lowered_flops_track_active_params():
    """2 x total_MACs of the FW lowering ~ 2*N_active*D within the
    attention + capacity-factor envelope, for dense and MoE archs."""
    for arch in ("smollm-135m", "phi3-mini-3.8b", "granite-moe-1b-a400m",
                 "deepseek-v2-lite-16b", "mamba2-2.7b"):
        cfg = get_config(arch)
        spec = ShapeSpec("t", 4096, 8, "prefill")     # FW only
        low = lower_block(cfg, spec)
        flops = 2 * low.total_macs()
        model = 2 * cfg.active_param_count() * 4096 * 8
        ratio = flops / model
        assert 0.8 <= ratio <= 3.0, (arch, ratio)


def test_training_triples_matmul_work():
    cfg = get_config("smollm-135m")
    fw = lower_block(cfg, ShapeSpec("p", 1024, 4, "prefill")).total_macs()
    tr = lower_block(cfg, ShapeSpec("t", 1024, 4, "train")).total_macs()
    assert tr == pytest.approx(3 * fw)


def test_decode_lowering_uses_kv_cache_length():
    cfg = get_config("smollm-135m")
    low = lower_block(cfg, SHAPES["decode_32k"])
    scores = [w for w in low.block_workloads() if w.name == "scores"]
    assert scores and scores[0].dims[1] == 32768  # M = kv_len
    # decode processes 1 token per sequence
    q = [w for w in low.block_workloads() if w.name == "q"][0]
    assert q.dims[0] == SHAPES["decode_32k"].global_batch


def test_factor_clip_divides():
    assert _factor_clip(48, 16) == 16
    assert _factor_clip(40, 16) == 10
    assert _factor_clip(7, 16) == 7
    assert _factor_clip(9, 4) == 3


def test_planner_prefers_token_sharding_for_tall_matmuls():
    # tall-skinny: tokens >> features => split N over the big data axis
    wl = matmul_workload(rows=1 << 20, cols=4096, inner=4096, name="mlp")
    best = plan_workload(wl, data_par=32, model_par=16)[0]
    assert best.data_dim == "N"
    assert best.model_dim in ("M", "C")


def test_planner_cell_and_overrides():
    cfg = get_config("nemotron-4-15b")
    plans = plan_cell(cfg, SHAPES["train_4k"], data_par=32, model_par=16)
    assert plans
    import jax
    mesh = jax.sharding.Mesh(
        __import__("numpy").array(jax.devices()[:1]).reshape(1, 1),
        ("data", "model"))
    ov = trim_sharding_overrides(cfg, SHAPES["train_4k"], mesh)
    assert isinstance(ov, dict)          # M-plan => {} (baseline TP)


def test_tpu_pod_desc_is_valid_trim_hardware():
    hw = make_tpu_pod_desc(256)
    assert hw.compute.num_pes == 256
    assert [lv.kind for lv in hw.levels] == ["memory", "routing", "memory",
                                             "compute"]
    assert hw.tiling_levels[1].fanout == 256


# sha256 (first 16 hex digits) of repr(all_workloads()) per shape, in
# SHAPES order, as one representative block x n_layers lowered them
# before the lowering took kinds of layer: configs with neither latent
# attention nor experts must keep every workload, in order.
PLAIN_LOWERINGS = {
    "phi3-mini-3.8b": ["665e7cdc566ce5c3", "201fca3dbe491466",
                       "491f05e1de4a109a", "e4b6da833d9bf351"],
    "nemotron-4-15b": ["3992428dec2b6e65", "67e802ec0a1b8bbf",
                       "59d4dd0dfee482b6", "d50e13b91b399bd6"],
    "smollm-135m": ["b9d93c97179b5c8b", "fc93da5574431f8f",
                    "e6ddac891e8eb4de", "60accb27ab1097d6"],
    "whisper-small": ["9a443772fbf6b1d2", "7f47bee23a48bd65",
                      "038e6ec259473221", "e75e7cd1e2c8e4d1"],
    "mamba2-2.7b": ["7eb93ca4147f597f", "a3ac299f95ff68c8",
                    "eb654df94da8e196", "c93597fd43f48543"],
    "qwen2-vl-2b": ["60d1cc09959893c3", "5f1d873c4ea4f568",
                    "94539ffc0dfa0a62", "52bc1052c8883ff6"],
    "zamba2-2.7b": ["ce6681db990ea36a", "85a873f5fc2988a8",
                    "d4c525ddc50c59d9", "7f1bf23cde27aa6c"],
}
EXPERT_LAYERS = ("expert_up", "expert_down")


def test_plain_lowerings_cover_the_registry():
    assert sorted(PLAIN_LOWERINGS) == sorted(
        n for n, c in ARCHS.items() if c.attn != "mla" and not c.n_experts)


@pytest.mark.parametrize("arch,shape", [
    (a, s) for a in PLAIN_LOWERINGS for s in SHAPES])
def test_plain_configs_lower_as_before(arch, shape):
    wls = lower_block(get_config(arch), SHAPES[shape]).all_workloads()
    digest = hashlib.sha256(repr(wls).encode()).hexdigest()[:16]
    assert digest == PLAIN_LOWERINGS[arch][list(SHAPES).index(shape)]


def test_deepseek_lowers_one_dense_then_26_moe_layers():
    cfg = get_config("deepseek-v2-lite-16b")
    low = lower_block(cfg, SHAPES["train_4k"])
    assert [n for _, n in low.kinds] == [1, 26]
    (dense, _), (moe, _) = low.kinds
    names = lambda wls: [w.name for w in wls if w.phase == "FW"]
    assert "router" not in names(dense) and "mlp_up" in names(dense)
    assert "mlp_up" not in names(moe) and "router" in names(moe)
    up = next(w for w in dense if w.name == "mlp_up")
    down = next(w for w in dense if w.name == "mlp_down")
    assert up.dims[1] == 2 * 10944 and down.dims[2] == 10944
    # schedule order: layer 0 is the dense layer, the head comes last
    every = low.all_workloads()
    assert every[:len(dense)] == dense
    assert every[len(dense):len(dense) + len(moe)] == moe
    assert [w.name for w in every[-3:]] == ["lm_head", "lm_head.BW",
                                           "lm_head.WG"]
    assert low.n_layers == 27 and len(every) == len(dense) + 26 * len(moe) + 3
    assert low.total_macs() == sum(w.macs for w in every)


@pytest.mark.parametrize("arch", ["deepseek-v2-lite-16b", "minicpm3-4b"])
@pytest.mark.parametrize("kind", ["train", "prefill", "decode"])
def test_mla_scores_are_causal_outside_decode(arch, kind):
    cfg = get_config(arch)
    b, s = 4, 1024
    low = lower_block(cfg, ShapeSpec("x", s, b, kind))
    sq = 1 if kind == "decode" else s
    rows = b * cfg.n_heads * sq // (1 if kind == "decode" else 2)
    for wls, _ in low.kinds:
        att = {w.name: w.dims for w in wls if w.phase == "FW"}
        assert att["scores"][:3] == (rows, s, cfg.qk_nope_dim
                                     + cfg.qk_rope_dim)
        assert att["attn_v"][:3] == (rows, cfg.v_head_dim, s)


def _expert_gemms(low):
    """-> Counter of (layer, phase, dims) of the routed-expert workloads
    over all layers."""
    return collections.Counter(
        (w.layer, w.phase, w.dims) for w in low.all_workloads()
        if w.layer in EXPERT_LAYERS)


def test_expert_shares_add_up_to_the_uncut_layer():
    """8 chips, each with 32 of train_4k's 256 sequences and 8 of the 64
    experts: their routed-expert GEMMs together are the uncut lowering's,
    and what every chip computes alike is the uncut's rows / 8."""
    cfg = get_config("deepseek-v2-lite-16b")
    share = lower_block(dataclasses.replace(cfg, experts_held=8),
                        ShapeSpec("share", 4096, 32, "train"))
    whole = lower_block(cfg, ShapeSpec("whole", 4096, 256, "train"))
    union = collections.Counter()
    for _ in range(8):
        union.update(_expert_gemms(share))
    assert union == _expert_gemms(whole)
    moe_fw = lambda low: {w.name: w.dims for w in low.kinds[-1][0]
                          if w.phase == "FW"}
    got, want = moe_fw(share), moe_fw(whole)
    assert sorted(n for n in want if n.startswith("expert_")) == sorted(
        f"expert_{p}.e{i}" for p in ("up", "down") for i in range(64))
    for name in ("q", "kv_a", "k_expand", "v_expand", "scores", "attn_v",
                 "o", "router", "shared_up", "shared_down"):
        assert got[name][0] * 8 == want[name][0], name
        assert got[name][1:] == want[name][1:], name


@pytest.mark.parametrize("arch,shape", [
    (a, s) for a, c in ARCHS.items() if c.n_experts
    for s in SHAPES if not is_skipped(c, s)])
def test_all_experts_keep_the_expert_macs(arch, shape):
    """At experts_held 0 the per-expert GEMMs hold what one matmul of
    t * top_k * capacity_factor rows held."""
    cfg = get_config(arch)
    spec = SHAPES[shape]
    low = lower_block(cfg, spec)
    t = spec.global_batch * (1 if spec.kind == "decode" else spec.seq_len)
    per_layer = int(t * cfg.top_k * cfg.capacity_factor) \
        * 3 * cfg.d_expert * cfg.d_model
    per_layer *= 3 if spec.kind == "train" else 1
    moe_layers = cfg.n_layers - cfg.first_dense_layers
    assert sum(w.macs for w in low.all_workloads()
               if w.layer in EXPERT_LAYERS) == per_layer * moe_layers
    assert sum(c for c in _expert_gemms(low).values()) \
        == moe_layers * 2 * cfg.n_experts * (3 if spec.kind == "train"
                                             else 1)


@pytest.mark.parametrize("held", [-1, 3, 128])
def test_experts_held_must_divide_the_experts(held):
    cfg = dataclasses.replace(get_config("deepseek-v2-lite-16b"),
                              experts_held=held)
    with pytest.raises(ValueError, match="experts_held"):
        lower_block(cfg, SHAPES["decode_32k"])


def test_planner_reads_every_kind_of_layer():
    cfg = get_config("deepseek-v2-lite-16b")
    n = len(lower_block(cfg, SHAPES["prefill_32k"]).block_workloads())
    plans = plan_cell(cfg, SHAPES["prefill_32k"], data_par=32,
                      model_par=16, top_workloads=n)
    assert len(plans) == n and "mlp_up" in plans and "router" in plans


def test_lower_span_reaches_the_active_tracer():
    from repro.obs import Tracer, activate
    cfg = dataclasses.replace(get_config("deepseek-v2-lite-16b"),
                              experts_held=8)
    tr = Tracer()
    with activate(tr):
        low = lower_block(cfg, ShapeSpec("share", 4096, 32, "train"))
    spans = [s for s in tr.buffer.snapshot() if s.name == "lower"]
    assert len(spans) == 1 and spans[0].t1 is not None
    every = low.all_workloads()
    assert len(every) == 2058
    assert spans[0].attrs == {
        "model": "deepseek-v2-lite-16b", "shape": "share", "kinds": 2,
        "layers": 27, "workloads": len(every),
        "shapes": len({(w.dims, w.kind) for w in every}),
        "expert_gemms": 26 * 8 * 2 * 3}
    assert spans[0].attrs["shapes"] == 41
