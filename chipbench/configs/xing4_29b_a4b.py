"""xing4_29b_a4b: `models.DecoderLM` on mHC's four residual streams (every
sublayer mixed by a pre-, a post- and a Sinkhorn-normalised residual matrix),
q-latent MLA with YaRN-rotated shared channels on flash's two head sizes, a
dense SwiGLU in the leading layer, then a sigmoid top-4 router over 64 experts,
of which this chip holds 8, beside a shared expert, and one multi-token
prediction module; next-token cross-entropy + 0.1 x the MTP head's under
AdamW in one `FusedTrainStep`, built from xing4_29b_a4b.json: one chip's share
of an ep8 group, the published layers 0 and 2-5.

The weights and the whole ring, in a fixed order, come from the file's
`weights_seed`: the runner's `--seed` changes nothing of a step's work (a
routed step's work follows its batches and their order, PERF.md §6;
with one sequence a batch there is no order inside a batch to draw).
"""
import math

import jax
import jax.numpy as jnp

# Model FLOPs of one training step (2 per multiply-add, backward = 2 x
# forward, nothing recomputed counted).  Per token 6 * N_dense, N_dense =
# every matrix a token passes whole (MLA's five projections in the six
# attention layers, the MTP's included; the dense layer; the routers and
# shared experts of the five routed layers; the MTP's `eh_proj`; the head over
# the vocabulary slice twice: the main head and the MTP head's pass; the
# embedding is a gather); 6 * 3*U*F per ROUTED ROW on a held expert, rows as
# the program's own counter read them on its last step (the balanced share
# where no counter is there); MLA's two T x T matmuls over the causal half,
# 1,920 per visible score and head (q.k 192 wide, v 128); mHC's projections
# and stream mixes (`mhc_flops`).
FLOP_CONVENTION = ("2 FLOPs per multiply-add; 6*N_dense per token (the head twice) + 6*3*U*F "
                   "per routed row on a held expert (program counter) + 1920*H per visible MLA "
                   "score + mHC's projections and stream mixes (mhc_flops)")


def layer_kinds(cfg):
    """[(attention kind, ffn kind)] of the published layers held, in
    `DecoderLM`'s names (the MTP module's layer is of the last one's)."""
    return [("latent_attention", "dense" if l < cfg["first_k_dense_replace"] else "sparse")
            for l in cfg["layers_held"]]


def rope(cfg):
    """The rotary table's entry for `rope_inv_freq`: YaRN as published, with
    cos and sin scaled by mscale / mscale_all_dim (1 here): the attention
    factor lives in the softmax scale (`softmax_scale`)."""
    rs = cfg["rope_scaling"]
    if rs["type"] != "yarn":
        raise ValueError("only the published YaRN rotary positions are built")
    return dict(rope_type="yarn", rope_theta=cfg["rope_theta"], factor=rs["factor"],
                original_max_position_embeddings=rs["original_max_position_embeddings"],
                beta_fast=rs["beta_fast"], beta_slow=rs["beta_slow"],
                attention_factor=_mscale(rs["factor"], rs["mscale"])
                / _mscale(rs["factor"], rs["mscale_all_dim"]))


def _mscale(factor, m):
    return 0.1 * m * math.log(factor) + 1.0 if factor > 1 else 1.0


def softmax_scale(cfg):
    """(qk_nope + qk_rope)^-1/2 x mscale(factor, mscale_all_dim)^2: 0.144680."""
    s = _mscale(cfg["rope_scaling"]["factor"], cfg["rope_scaling"]["mscale_all_dim"])
    return (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5 * s * s


def model_arguments(cfg):
    """`DecoderLM`'s arguments from the published keys."""
    if cfg["n_group"] != 1 or cfg["topk_group"] != 1 or cfg["n_shared_experts"] != 1 \
            or cfg["scoring_func"] != "sigmoid" or cfg["topk_method"] != "noaux_tc" \
            or cfg["num_nextn_predict_layers"] != 1:
        raise ValueError("only the published form is built: one expert group, one shared "
                         "expert, the sigmoid noaux_tc router, one MTP module")
    kinds = layer_kinds(cfg)
    return dict(
        vocab_size=cfg["vocab_size"], units=cfg["hidden_size"],
        layer_types=[a for a, _f in kinds], mlp_layer_types=[f for _a, f in kinds],
        mla=dict(num_heads=cfg["num_attention_heads"],
                 qk_nope_head_dim=cfg["qk_nope_head_dim"],
                 qk_rope_head_dim=cfg["qk_rope_head_dim"], v_head_dim=cfg["v_head_dim"],
                 kv_lora_rank=cfg["kv_lora_rank"], q_lora_rank=cfg["q_lora_rank"],
                 rope=rope(cfg), softmax_scale=softmax_scale(cfg)),
        dense_hidden=cfg["intermediate_size"], expert_hidden=cfg["moe_intermediate_size"],
        shared_hidden=cfg["moe_intermediate_size"] * cfg["n_shared_experts"],
        num_experts=cfg["router_experts"], top_k=cfg["num_experts_per_tok"],
        experts_held=cfg["n_routed_experts"], ep_rank=cfg["ep_rank"],
        router=dict(scoring="sigmoid", renormalize=cfg["norm_topk_prob"],
                    scaling_factor=cfg["routed_scaling_factor"],
                    picks_at_once=cfg["routed_picks_at_once"]),
        hc_mult=cfg["hc_mult"],
        hc=dict(iters=cfg["hc_sinkhorn_iters"], eps=cfg["hc_eps"],
                clamp=(cfg["mhc_h_res_clamp_min"], cfg["mhc_h_res_clamp_max"])),
        mtp=dict(weight=cfg["mtp_loss_weight"]),
        epsilon=cfg["rms_norm_eps"], dtype=cfg["dtype"], remat=cfg["remat"])


def build(cfg):
    """(model with its loss, trainer); weights from `weights_seed`."""
    import mxnet_tpu as mx
    from mxnet_tpu import telemetry
    from mxnet_tpu.models import CausalLMLoss, DecoderLM

    kinds = layer_kinds(cfg)
    with telemetry.span("decoder.build", layers=len(kinds),
                        kinds=",".join(f"{a}+{f}" for a, f in kinds)):
        mx.random.seed(cfg["weights_seed"])     # not the runner's seed
        model = DecoderLM(**model_arguments(cfg))
        model.initialize()
    return CausalLMLoss(model), mx.gluon.Trainer(
        model.collect_params(), cfg["optimizer"], dict(cfg["optimizer_params"]))


def make_ring(cfg, cell, chips, seed, sharding):
    """`ring` batches of `batch` sequences of `seq_len` ids, uniform over the
    vocabulary slice, made on the device: [((ids,), tokens)].  Every batch,
    in a fixed order, from `weights_seed`; `seed` draws nothing."""
    from mxnet_tpu.ndarray.ndarray import NDArray
    del seed
    n, shape = cell["ring"], (cell["batch"] * chips, cell["seq_len"])

    def make(key):
        return [jax.random.randint(k, shape, 0, cfg["vocab_size"], jnp.int32)
                for k in jax.random.split(key, n)]

    made = jax.jit(make, out_shardings=sharding)(jax.random.key(cfg["weights_seed"]))
    return [((NDArray(ids),), shape[0] * shape[1]) for ids in made]


def _layer_params(p, pre, ffn):
    """One layer's reference parameters (`xing4_29b_a4b_reference.py`) from the
    model's `{name: f32 array}`, `pre` the layer's name with its dot."""
    a = pre + "attend.attention."
    lp = {"norm1": p[pre + "attend.norm.gamma"], "norm2": p[pre + "ffn_norm.gamma"],
          "q_norm": p[a + "q_norm.gamma"], "kv_norm": p[a + "kv_norm.gamma"]}
    lp.update({ref: p[a + mine + ".weight"].T for ref, mine in (
        ("wqa", "q_a"), ("wqb", "q_b"), ("wkva", "kv_a"), ("wkvb", "kv_b"), ("wo", "o_proj"))})
    for ref, mine in (("hc_attn", "attend.hc."), ("hc_ffn", "ffn_hc.")):
        lp[ref] = {"gain": p[pre + mine + "gamma"], "phi": p[pre + mine + "phi"],
                   "alpha": p[pre + mine + "alpha"], "bias": p[pre + mine + "bias"]}
    if ffn == "dense":
        lp.update({k: p[pre + f"mlp.{k}.weight"].T for k in ("gate", "up", "down")})
    else:
        lp.update(router=p[pre + "experts.router"], bias=p[pre + "experts.correction_bias"],
                  gate=p[pre + "experts.gate"], up=p[pre + "experts.up"],
                  down=p[pre + "experts.down"])
        lp.update({"shared_" + k: p[pre + f"shared.{k}.weight"].T for k in ("gate", "up", "down")})
    return lp


def reference_params(p, cfg):
    """The reference's parameter tree from the model's own `{name: f32
    array}`: names mapped, Dense weights transposed."""
    kinds = layer_kinds(cfg)
    return {"embed": p["embed.weight"], "norm": p["norm.gamma"], "head": p["head.weight"].T,
            "layers": [_layer_params(p, f"layer{l}.", f) for l, (_a, f) in enumerate(kinds)],
            "mtp": {"hnorm": p["mtp.hnorm.gamma"], "enorm": p["mtp.enorm.gamma"],
                    "eh": p["mtp.eh_proj.weight"].T, "norm": p["mtp.norm.gamma"],
                    "layer": _layer_params(p, "mtp.layer.", kinds[-1][1])}}


def reference_config(cfg):
    """The configuration as the reference reads it: the router's full width under
    `n_routed_experts` (the share is an argument of its functions)."""
    return dict(cfg, n_routed_experts=cfg["router_experts"])


def walk(model, ids):
    """((the main head's logits, the MTP head's), [(layer, its FFN's normed
    input)] of every layer in order, the MTP module's last), the model's own
    blocks called one after another: the picks `chip_smoke.py` gives the
    reference are made from them."""
    from mxnet_tpu.models.decoder import _expand, _hc_combine, _merge
    from mxnet_tpu.ndarray.ndarray import NDArray

    def layer(lyr, x, inputs):
        x = lyr.attend(x)
        u, post, res = lyr.ffn_hc(x)
        m = lyr.ffn_norm(u)
        inputs.append((lyr, m))
        y = sum((getattr(lyr, part)(m)._data for part in lyr._ffn[1:]),
                getattr(lyr, lyr._ffn[0])(m)._data)
        return NDArray(_hc_combine(x._data, y, post._data, res._data))

    inputs = []
    e = model.embed(ids)
    x = _expand(e, model._streams)
    for name in model._layer_names:
        x = layer(getattr(model, name), x, inputs)
    h = _merge(x)
    mtp = model.mtp
    after = NDArray(jnp.roll(e._data, -1, axis=1))
    z = mtp.eh_proj(NDArray(jnp.concatenate(
        [mtp.hnorm(h)._data, mtp.enorm(after)._data], axis=-1)))
    ahead = mtp.norm(_merge(layer(mtp.layer, _expand(z, model._streams), inputs)))
    return (model.head(model.norm(h)), model.head(ahead)), inputs


def tokens(cell, chips):
    return cell["batch"] * chips * cell["seq_len"]


def count_layers(cfg, kind):
    """Layers of a kind, the MTP module's layer among them."""
    kinds = layer_kinds(cfg)
    return sum(kind in pair for pair in kinds + kinds[-1:])


def attention_flops(cfg, cell, chips):
    """Forward + backward FLOPs of the MLA layers' attention cores over the causal
    half: QK^T (2 * 192) and PV (2 * 128) forward, dV, dP (2 * 128 each), dQ, dK
    (2 * 192 each) backward: 1,920 per visible score and head; six layers, the
    MTP module's included."""
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


def sublayers(cfg):
    """mHC sublayers a step runs: two in each layer, the MTP module's too."""
    return 2 * (len(cfg["layers_held"]) + cfg["num_nextn_predict_layers"])


def mhc_flops(cfg, cell, chips):
    """Forward + backward FLOPs of mHC: per sublayer and token, the projection
    of the flat n*U row onto the 2n + n^2 logits (2 n U (2n + n^2)), the norm
    over that row (4 n U: square, sum, scale, gain), the pre-mix H_pre X (2 n U),
    the residual mix H_res X (2 n^2 U) and the post-add H_post^T y (2 n U), and
    Sinkhorn's 2 x `hc_sinkhorn_iters` normalisations of the n x n matrix (2 n^2
    each: a sum and a divide); forward once, the backward pass twice that,
    recomputed work not counted."""
    n, u = cfg["hc_mult"], cfg["hidden_size"]
    width = 2 * n + n * n
    forward = 2 * n * u * width + 4 * n * u + 2 * n * u + 2 * n * n * u + 2 * n * u \
        + 2 * cfg["hc_sinkhorn_iters"] * 2 * n * n
    return float(3 * forward * sublayers(cfg) * tokens(cell, chips))


def mhc_bytes(cfg, cell, chips):
    """HBM bytes mHC must move, each stream tensor (n x U per token, bf16) read
    or written once per pass that must touch it, recomputed work once.  Per
    sublayer and token: forward, X in and X' out, the sublayer's input u out
    and its output y in (U each); backward, X and dX' in and dX out, du in
    and dy out.  The model's two sets of streams (the main layers' and the MTP
    module's) are each expanded from one row and summed back: forward, X_0
    out and X in; backward, dX in and dX_0 out.  The mixes themselves (2n + n^2
    floats a token) are not counted."""
    n, u = cfg["hc_mult"], cfg["hidden_size"]
    stream, row = 2 * n * u, 2 * u
    per_sublayer = (2 * stream + 2 * row) + (3 * stream + 2 * row)
    ends = 2 * (2 * stream + 2 * stream)
    return float((per_sublayer * sublayers(cfg) + ends) * tokens(cell, chips))


def expert_flops(cfg, rows):
    """6 * 3*U*F for every routed row on a held expert."""
    return 18.0 * cfg["hidden_size"] * cfg["moe_intermediate_size"] * rows


def expert_bytes(cfg, rows):
    """The held experts' three matrices read forward and twice backward and
    their gradients written; each row's input, two hidden rows and output
    read or written in each of the three passes."""
    u, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    weights = 4 * count_layers(cfg, "sparse") * cfg["n_routed_experts"] * 3 * u * f * 2
    return float(weights + 3 * rows * (2 * u + 3 * f) * 2)


def balanced_rows(cfg, cell, chips):
    return tokens(cell, chips) * cfg["num_experts_per_tok"] * count_layers(cfg, "sparse") \
        * cfg["n_routed_experts"] // cfg["router_experts"]


def routed_rows(cfg, cell, chips):
    """Rows on held experts over all layers in the last step, from the
    program's counters; the balanced share where they read nothing."""
    from chipbench import layer_work
    return layer_work.routed_rows() or balanced_rows(cfg, cell, chips)


def dense_parameters(cfg):
    """Weights of every matrix a token passes whole (mHC's projections are
    counted by `mhc_flops`)."""
    u, h = cfg["hidden_size"], cfg["num_attention_heads"]
    dn, dr, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    rq, rkv = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    mla = u * rq + rq * h * (dn + dr) + u * (rkv + dr) + rkv * h * (dn + dv) + h * dv * u
    sparse = u * cfg["router_experts"] + 3 * u * cfg["moe_intermediate_size"] \
        * cfg["n_shared_experts"]
    return count_layers(cfg, "latent_attention") * mla + count_layers(cfg, "sparse") * sparse \
        + count_layers(cfg, "dense") * 3 * u * cfg["intermediate_size"] \
        + cfg["num_nextn_predict_layers"] * 2 * u * u \
        + (1 + cfg["num_nextn_predict_layers"]) * u * cfg["vocab_size"]


def dense_flops(cfg, cell, chips):
    return 6.0 * dense_parameters(cfg) * tokens(cell, chips)


def flops_per_step(cfg, cell, chips, mod):
    return dense_flops(cfg, cell, chips) + attention_flops(cfg, cell, chips) \
        + mhc_flops(cfg, cell, chips) + expert_flops(cfg, routed_rows(cfg, cell, chips))
