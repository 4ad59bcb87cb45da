"""DeepSeek-V2-Lite lowered to matrix multiplications, written from its
published config.json (huggingface.co/deepseek-ai/DeepSeek-V2-Lite).

Each layer is multi-head latent attention, causal: the query projected
from the hidden state (no query compression), keys and values through a
compressed latent with a decoupled rotary key, and the latent expanded
to per-head keys and values over every cached position.  Layer 0 has a
dense SwiGLU MLP; every later layer has a router over all routed
experts, the routed experts this chip holds, each its own GEMM pair, and
the shared experts, through which every token goes, as one pair of
their summed width.  Then the LM head.  In training each product is also
its input-gradient (BW) and weight-gradient (WG) product.

A chip that holds `experts_held` of the routed experts is one of
`ROUTED / experts_held` that share each layer by expert parallelism:
each of its experts takes the tokens routed to it from all of them,
`t * TOP_K * CAPACITY / experts_held` rows for `t` tokens on each chip,
rounded up."""
from bench.reference.trim.task_analyst import TaskWorkloads
from bench.reference.trim.workload import matmul_workload

HIDDEN = 2048          # hidden_size
HEADS = 16             # num_attention_heads
KV_LORA_RANK = 512     # kv_lora_rank (q_lora_rank: null)
QK_NOPE = 128          # qk_nope_head_dim
QK_ROPE = 64           # qk_rope_head_dim
V_HEAD = 128           # v_head_dim
DENSE_FFN = 10944      # intermediate_size
EXPERT_FFN = 1408      # moe_intermediate_size
ROUTED = 64            # n_routed_experts
TOP_K = 6              # num_experts_per_tok
SHARED = 2             # n_shared_experts
FIRST_DENSE = 1        # first_k_dense_replace
LAYERS = 27            # num_hidden_layers
VOCAB = 102400         # vocab_size
CAPACITY = (5, 4)      # capacity factor 1.25 (assumed), as a fraction


def _phases(mms, training):
    fw = [matmul_workload(rows=r, cols=c, inner=k, name=n)
          for n, r, c, k in mms]
    if not training:
        return fw
    grads = []
    for n, r, c, k in mms:
        grads.append(matmul_workload(rows=r, cols=k, inner=c,
                                     name=n + ".BW", phase="BW"))
        grads.append(matmul_workload(rows=k, cols=c, inner=r,
                                     name=n + ".WG", phase="WG"))
    return fw + grads


def task(config):
    net = config["network"]
    shape = net["shape"]
    overrides = net.get("overrides", {})
    b, s, kind = shape["global_batch"], shape["seq_len"], shape["kind"]
    layers = overrides.get("n_layers", LAYERS)
    held = overrides.get("experts_held", 0) or ROUTED
    decode = kind == "decode"
    sq = 1 if decode else s
    t = b * sq
    att = b * HEADS * sq if decode else b * HEADS * sq // 2
    expanded = t if decode else b * s
    attention = [("q", t, HEADS * (QK_NOPE + QK_ROPE), HIDDEN),
                 ("kv_a", t, KV_LORA_RANK + QK_ROPE, HIDDEN),
                 ("k_expand", expanded, HEADS * QK_NOPE, KV_LORA_RANK),
                 ("v_expand", expanded, HEADS * V_HEAD, KV_LORA_RANK),
                 ("scores", att, s, QK_NOPE + QK_ROPE),
                 ("attn_v", att, V_HEAD, s),
                 ("o", t, HIDDEN, HEADS * V_HEAD)]
    dense = attention + [("mlp_up", t, 2 * DENSE_FFN, HIDDEN),
                         ("mlp_down", t, HIDDEN, DENSE_FFN)]
    routed_tokens = t * TOP_K * CAPACITY[0] // CAPACITY[1]
    rows = -(-routed_tokens // held)
    moe = attention + [("router", t, ROUTED, HIDDEN)]
    for i in range(held):
        moe += [(f"expert_up.e{i}", rows, 2 * EXPERT_FFN, HIDDEN),
                (f"expert_down.e{i}", rows, HIDDEN, EXPERT_FFN)]
    moe += [("shared_up", t, 2 * SHARED * EXPERT_FFN, HIDDEN),
            ("shared_down", t, HIDDEN, SHARED * EXPERT_FFN)]
    n_dense = min(FIRST_DENSE, layers)
    training = kind == "train"
    intra = (_phases(dense, training) * n_dense
             + _phases(moe, training) * (layers - n_dense)
             + _phases([("lm_head", t, VOCAB, HIDDEN)], training))
    return TaskWorkloads(intra=intra, preproc=[], activations=[])
