"""kimi_linear_48b_a3b: `models.DecoderLM` (Kimi Delta Attention as a chunked
scan in three layers of four, NoPE latent attention with 192-wide q.k and
128-wide v heads in the fourth; a dense SwiGLU in the leading layer, then a
sigmoid top-8 router over 256 experts, of which this chip holds 8, beside a
shared expert) with next-token cross-entropy under AdamW in one
`FusedTrainStep`, built from kimi_linear_48b_a3b.json: one chip's share of an
ep32 group, the published layers 1-5.

The weights and ring batch 0 come from the file's `weights_seed`, NOT from the
runner's `--seed` (which draws batches 1-3): how many routed rows land on the
held experts follows the router's weights (PERF_LEDGER, PR 27), and the first
warm-up loss is one number for every seed.
"""
import jax
import jax.numpy as jnp

# Model FLOPs of one training step (2 per multiply-add, backward = 2 x
# forward, nothing recomputed counted).  Per token 6 * N_dense, N_dense =
# every matrix a token passes whole (KDA's and MLA's projections and gates,
# the routers, the shared experts, the dense layer, the head over the
# vocabulary slice; the embedding is a gather, the 4-tap convolutions are
# not matmuls); 6 * 3*U*F per ROUTED ROW on a held expert, rows as the
# program's own counter read them on its last step (the balanced share where
# no counter is there); MLA's two T x T matmuls over the causal half, 1,920
# per visible score and head (q.k 192 wide, v 128); KDA's core in its chunked
# form at C = 64 (`kda_flops`).
FLOP_CONVENTION = ("2 FLOPs per multiply-add; 6*N_dense per token + 6*3*U*F per routed row on a "
                   "held expert (program counter) + 1920*H per visible MLA score + KDA's chunked "
                   "form at C=64 (3 x 2 x (C^2 (K+V) + 3 C K V) per chunk and head)")
CHUNK = 64


def layer_kinds(cfg):
    """[(attention kind, ffn kind)] of the published layers 1..num_hidden_layers,
    in `DecoderLM`'s names."""
    lin = cfg["linear_attn_config"]
    kinds = []
    for l in range(1, cfg["num_hidden_layers"] + 1):
        if (l in lin["kda_layers"]) == (l in lin["full_attn_layers"]):
            raise ValueError(f"layer {l} is in both or neither of kda_layers and full_attn_layers")
        kinds.append(("kda" if l in lin["kda_layers"] else "latent_attention",
                      "dense" if l <= cfg["first_k_dense_replace"] else "sparse"))
    return kinds


def model_arguments(cfg):
    """`DecoderLM`'s arguments from the published keys."""
    if not cfg["mla_use_nope"] or cfg["q_lora_rank"] is not None or cfg["num_expert_group"] != 1 \
            or cfg["topk_group"] != 1 or cfg["num_shared_experts"] != 1:
        raise ValueError("only the published form is built: NoPE latent attention without a "
                         "q latent, one expert group, one shared expert")
    lin, kinds = cfg["linear_attn_config"], layer_kinds(cfg)
    return dict(
        vocab_size=cfg["vocab_size"], units=cfg["hidden_size"],
        layer_types=[a for a, _f in kinds], mlp_layer_types=[f for _a, f in kinds],
        kda=dict(num_heads=lin["num_heads"], head_dim=lin["head_dim"],
                 conv_size=lin["short_conv_kernel_size"], chunk=CHUNK),
        mla=dict(num_heads=cfg["num_attention_heads"],
                 qk_nope_head_dim=cfg["qk_nope_head_dim"],
                 qk_rope_head_dim=cfg["qk_rope_head_dim"], v_head_dim=cfg["v_head_dim"],
                 kv_lora_rank=cfg["kv_lora_rank"]),
        dense_hidden=cfg["intermediate_size"], expert_hidden=cfg["moe_intermediate_size"],
        shared_hidden=cfg["moe_intermediate_size"] * cfg["num_shared_experts"],
        num_experts=cfg["num_experts_routed"], top_k=cfg["num_experts_per_token"],
        experts_held=cfg["num_experts"], ep_rank=cfg["ep_rank"],
        router=dict(scoring=cfg["moe_router_activation_func"],
                    renormalize=cfg["moe_renormalize"],
                    scaling_factor=cfg["routed_scaling_factor"]),
        epsilon=cfg["rms_norm_eps"], dtype=cfg["dtype"], remat=cfg["remat"])


def build(cfg):
    """(model with its loss, trainer); weights from `weights_seed`."""
    import mxnet_tpu as mx
    from mxnet_tpu import telemetry
    from mxnet_tpu.models import CausalLMLoss, DecoderLM

    kinds = layer_kinds(cfg)
    with telemetry.span("decoder.build", layers=cfg["num_hidden_layers"],
                        kinds=",".join(f"{a}+{f}" for a, f in kinds)):
        mx.random.seed(cfg["weights_seed"])     # not the runner's seed
        model = DecoderLM(**model_arguments(cfg))
        model.initialize()
    return CausalLMLoss(model), mx.gluon.Trainer(
        model.collect_params(), cfg["optimizer"], dict(cfg["optimizer_params"]))


def make_ring(cfg, cell, chips, seed, sharding):
    """`ring` batches of `batch` sequences of `seq_len` ids, uniform over the
    vocabulary slice, made on the device: [((ids,), tokens)].  Batch 0 (the
    warm-up batch, whose first loss `correct` checks against the reference's)
    from `weights_seed`, the others from `--seed`."""
    from mxnet_tpu.ndarray.ndarray import NDArray
    n, shape = cell["ring"], (cell["batch"] * chips, cell["seq_len"])

    def make(first, rest):
        return [jax.random.randint(k, shape, 0, cfg["vocab_size"], jnp.int32)
                for k in [first] + list(jax.random.split(rest, n - 1))]

    made = jax.jit(make, out_shardings=sharding)(
        jax.random.key(cfg["weights_seed"]), jax.random.key(seed))
    return [((NDArray(ids),), shape[0] * shape[1]) for ids in made]


def reference_params(p, cfg):
    """The reference's parameter tree (`kimi_linear_48b_a3b_reference.py`) from
    the model's own `{name: f32 array}`: names mapped, Dense weights transposed."""
    layers = []
    for l, (attn, ffn) in enumerate(layer_kinds(cfg)):
        a, f = f"layer{l}.attend.attention.", f"layer{l}."
        lp = {"norm1": p[f"layer{l}.attend.norm.gamma"], "norm2": p[f + "ffn_norm.gamma"]}
        if attn == "kda":
            lp.update({ref: p[a + mine + ".weight"].T for ref, mine in (
                ("wq", "q_proj"), ("wk", "k_proj"), ("wv", "v_proj"), ("wf_a", "f_a"),
                ("wf_b", "f_b"), ("wb", "b_proj"), ("wg_a", "g_a"), ("wg_b", "g_b"),
                ("wo", "o_proj"))})
            lp.update(conv_q=p[a + "q_conv"], conv_k=p[a + "k_conv"], conv_v=p[a + "v_conv"],
                      A_log=p[a + "A_log"], dt_bias=p[a + "dt_bias"], bg=p[a + "g_b.bias"],
                      o_norm=p[a + "o_norm"])
        else:
            lp.update(wq=p[a + "q_proj.weight"].T, wkva=p[a + "kv_a.weight"].T,
                      kv_norm=p[a + "kv_norm.gamma"], wkvb=p[a + "kv_b.weight"].T,
                      wo=p[a + "o_proj.weight"].T)
        if ffn == "dense":
            lp.update({k: p[f + f"mlp.{k}.weight"].T for k in ("gate", "up", "down")})
        else:
            lp.update(router=p[f + "experts.router"], bias=p[f + "experts.correction_bias"],
                      gate=p[f + "experts.gate"], up=p[f + "experts.up"],
                      down=p[f + "experts.down"])
            lp.update({"shared_" + k: p[f + f"shared.{k}.weight"].T
                       for k in ("gate", "up", "down")})
        layers.append(lp)
    return {"embed": p["embed.weight"], "norm": p["norm.gamma"], "head": p["head.weight"].T,
            "layers": layers}


def reference_config(cfg):
    """The configuration as the reference reads it: the router's full width under
    `num_experts` (the share is an argument of its functions)."""
    return dict(cfg, num_experts=cfg["num_experts_routed"])


def tokens(cell, chips):
    return cell["batch"] * chips * cell["seq_len"]


def count_layers(cfg, kind):
    return sum(kind in pair for pair in layer_kinds(cfg))


def attention_flops(cfg, cell, chips):
    """Forward + backward FLOPs of the MLA layers' attention cores over the causal
    half: QK^T (2 * 192) and PV (2 * 128) forward, dV, dP (2 * 128 each), dQ, dK
    (2 * 192 each) backward: 1,920 per visible score and head."""
    t = cell["seq_len"]
    dqk, dv = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    per_score = 2 * (dqk + dv) + 2 * (2 * dv + 2 * dqk)
    return float(per_score * cfg["num_attention_heads"] * cell["batch"] * chips
                 * (t * (t + 1) // 2) * count_layers(cfg, "latent_attention"))


def attention_bytes(cfg, cell, chips):
    """HBM bytes the three flash kernels of every MLA layer must move once: q, k
    (192 wide), v in and o out (128 wide) forward; q, k, v, o, do in and dq, dk,
    dv out backward; bf16."""
    dqk, dv = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    per_token_head = (2 * dqk + 2 * dv) + (2 * dqk + 3 * dv) + (2 * dqk + dv)
    return float(2 * per_token_head * cfg["num_attention_heads"] * tokens(cell, chips)
                 * count_layers(cfg, "latent_attention"))


def kda_flops(cfg, cell, chips):
    """Forward + backward FLOPs of KDA's core in its chunked form, whatever
    implements it.  Per chunk of C rows and head, K = V = the head size, in
    multiply-adds: A and B over the causal half, C^2 K together; the triangular
    solve against V's columns, C^2 V / 2; B U over the causal half, C^2 V / 2;
    (Gamma K) S_0, (Gamma Q) S_0 and the state's update, 3 C K V.  Forward once;
    the backward pass is twice that; the recomputed forward is not counted."""
    lin = cfg["linear_attn_config"]
    c, k = CHUNK, lin["head_dim"]
    per_chunk = c * c * (k + k) + 3 * c * k * k
    chunks = -(-cell["seq_len"] // c) * cell["batch"] * chips
    return float(3 * 2 * per_chunk * chunks * lin["num_heads"] * count_layers(cfg, "kda"))


def kda_bytes(cfg, cell, chips):
    """HBM bytes KDA's core must move once per layer: q, k, v (bf16), the log
    decay g (f32, per key channel) and beta (f32, per head) in and o out
    forward; the same six and o's cotangent in, the five cotangents out
    backward.  The per-chunk states stay on the chip in this count."""
    lin = cfg["linear_attn_config"]
    d = lin["head_dim"]
    inputs = 3 * d * 2 + d * 4 + 4
    per_token_head = (inputs + d * 2) + (inputs + d * 2) + inputs
    return float(per_token_head * lin["num_heads"] * tokens(cell, chips)
                 * count_layers(cfg, "kda"))


def expert_flops(cfg, rows):
    """6 * 3*U*F for every routed row on a held expert."""
    return 18.0 * cfg["hidden_size"] * cfg["moe_intermediate_size"] * rows


def expert_bytes(cfg, rows):
    """The held experts' three matrices read forward and twice backward and
    their gradients written; each row's input, two hidden rows and output
    read or written in each of the three passes."""
    u, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    weights = 4 * count_layers(cfg, "sparse") * cfg["num_experts"] * 3 * u * f * 2
    return float(weights + 3 * rows * (2 * u + 3 * f) * 2)


def balanced_rows(cfg, cell, chips):
    return tokens(cell, chips) * cfg["num_experts_per_token"] * count_layers(cfg, "sparse") \
        * cfg["num_experts"] // cfg["num_experts_routed"]


def routed_rows(cfg, cell, chips):
    """Rows on held experts over all layers in the last step, from the
    program's counters; the balanced share where they read nothing."""
    from chipbench import layer_work
    return layer_work.routed_rows() or balanced_rows(cfg, cell, chips)


def dense_parameters(cfg):
    """Weights of every matrix a token passes whole."""
    u, lin = cfg["hidden_size"], cfg["linear_attn_config"]
    inner, rank = lin["num_heads"] * lin["head_dim"], lin["head_dim"]
    kda = 3 * u * inner + 2 * (u * rank + rank * inner) + u * lin["num_heads"] + inner * u
    h, dqk = cfg["num_attention_heads"], cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    mla = u * h * dqk + u * (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) \
        + cfg["kv_lora_rank"] * h * (cfg["qk_nope_head_dim"] + cfg["v_head_dim"]) \
        + h * cfg["v_head_dim"] * u
    sparse = u * cfg["num_experts_routed"] \
        + 3 * u * cfg["moe_intermediate_size"] * cfg["num_shared_experts"]
    return count_layers(cfg, "kda") * kda + count_layers(cfg, "latent_attention") * mla \
        + count_layers(cfg, "sparse") * sparse \
        + count_layers(cfg, "dense") * 3 * u * cfg["intermediate_size"] + u * cfg["vocab_size"]


def dense_flops(cfg, cell, chips):
    return 6.0 * dense_parameters(cfg) * tokens(cell, chips)


def flops_per_step(cfg, cell, chips, mod):
    return dense_flops(cfg, cell, chips) + attention_flops(cfg, cell, chips) \
        + kda_flops(cfg, cell, chips) + expert_flops(cfg, routed_rows(cfg, cell, chips))
