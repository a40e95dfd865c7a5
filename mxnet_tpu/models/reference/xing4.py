"""Xing4.0-29B-A4B, as its config.json and three papers describe it, in plain
`jax.numpy` float32: forward (both heads), both losses and (by `jax.grad`)
gradients.

Written from the layer equations (the catalog row of
https://huggingface.co/XingChen-AGI/Xing4.0-29B-A4B/blob/main/config.json; mHC,
arXiv:2512.24880; DeepSeek-V2, arXiv:2405.04434 §2.1; DeepSeek-V3,
arXiv:2412.19437 §2.1.2 and §2.2), not from the system's code: the residual
streams as whole (n, U) matrices per token, latent attention as dense masked
attention, the router and experts as a Python loop over the experts held, no
kernel, no sort, no cache, no remat.  Every matmul runs under
`jax.default_matmul_precision("highest")` (on a TPU an f32 matmul is otherwise
computed in bf16 passes).

Notation: U = `hidden_size`, n = `hc_mult`, eps = `hc_eps`.

mHC residual path.  The state of a token is X (n, U); the embedding enters as
n copies, X_0 = 1_n e^T.  Each sublayer F (attention, or the FFN, each with its
own pre-norm) does

    x^     = RMSNorm(vec X)                      a row of n U, a gain of n U
    z      = x^ phi                              phi (n U, n + n + n^2)
    H_pre  = sigmoid(alpha_pre  z_pre  + b_pre)                 (n,)
    H_post = 2 sigmoid(alpha_post z_post + b_post)              (n,)
    H_res  = Sinkhorn-Knopp(exp(clamp(alpha_res mat(z_res) + b_res, -30, 30)))
             `hc_sinkhorn_iters` alternating row and column normalisations
    X     <- H_res X + H_post^T F(H_pre X)

and after the last layer the streams are summed (1_n^T X), normed, and sent
through the head.

MLA (DeepSeek-V2 with a q latent and decoupled rotary positions), on u = H_pre X:
q = W_qb RMSNorm(W_qa RMSNorm_1(u)) in heads of `qk_nope_head_dim` +
`qk_rope_head_dim`; [c | k_r] = W_kva RMSNorm_1(u), c the `kv_lora_rank`
latent; [k_nope | v] = W_kvb RMSNorm(c) per head; q_r and the one shared k_r
rotated by position under YaRN (`rope_scaling`); scores scaled by
(qk_nope + qk_rope)^-1/2 x mscale^2, mscale = 0.1 mscale_all_dim ln(factor) + 1
(DeepSeek's convention; cos and sin scaled by mscale / mscale_all_dim = 1);
causal softmax; y = W_o of the heads' `v_head_dim` outputs.

FFN.  Published layers below `first_k_dense_replace` are a dense SwiGLU of
`intermediate_size`; the others route: s = sigmoid(W_r m) over all
`n_routed_experts` (`scoring_func`), the picks are the `num_experts_per_tok`
largest of s + correction bias (`noaux_tc`; n_group = topk_group = 1: no
group limit), weighted by their unbiased s divided by the picks' sum
(`norm_topk_prob`) times `routed_scaling_factor`; `n_shared_experts` shared
SwiGLU expert(s) of `moe_intermediate_size` beside them.

MTP (DeepSeek-V3 §2.2, `num_nextn_predict_layers` = 1).  h' = W_eh
[RMSNorm(h_t) ; RMSNorm(Emb(t+1))] for t = 0..T-2, h_t the main model's summed
final state before its norm; h' expanded into n streams, one routed decoder
layer on the same mHC path, summed, normed, and through the shared head: the
logits of token t+2.  loss = CE_main + lambda CE_mtp, CE_mtp over positions
0..T-3.

One chip's share of a deployment is a parameter here exactly as in the
system: `experts_held` contiguous experts from `ep_rank * experts_held` (the
router still scores all `n_routed_experts` and picks `num_experts_per_tok`;
what the absent experts would add is left out; the shared expert is whole),
and a vocabulary slice (the embedding and the head simply have that many
rows).  `layers_held` names the published layers kept (0-indexed); where the
configuration has none, the first `num_hidden_layers`.

    params = {"embed": (V, U), "norm": (U,), "head": (U, V), "layers": [
      {"hc_attn" "hc_ffn": {"gain": (n U,), "phi": (n U, 2n + n^2),
                            "alpha": (3,), "bias": (2n + n^2,)},
       "norm1": (U,), "norm2": (U,),
       "wqa": (U, Rq), "q_norm": (Rq,), "wqb": (Rq, H (dn + dr)),
       "wkva": (U, Rkv + dr), "kv_norm": (Rkv,), "wkvb": (Rkv, H (dn + dv)),
       "wo": (H dv, U),
       # dense FFN
       "gate" "up": (U, F), "down": (F, U),
       # or routed + shared
       "router": (U, E), "bias": (E,), "gate" "up": (held, U, F),
       "down": (held, F, U), "shared_gate" "shared_up": (U, F),
       "shared_down": (F, U)}, ...],
      "mtp": {"hnorm": (U,), "enorm": (U,), "eh": (2U, U), "layer": {...},
              "norm": (U,)}}

`assumed` (the config and the papers leave them open): the mHC norm has a
learned gain of n U (ones at initialisation) and the eps of `rms_norm_eps`;
alpha starts at 0.01 and b at zero (the mixes then start uniform: a plain
residual on n equal copies); Sinkhorn's first row normalisation is a softmax
(the row's max subtracted) and `hc_eps` is added to every later denominator
(exp(z) / (sum + eps) would leave a row of logits at -30, sum 4e-13, almost
unnormalised); the MTP weight lambda = 0.1 (`mtp_loss_weight`, DeepSeek-V3's
later value); the MTP input's two norms have gains of their own and the MTP
output a norm of its own before the shared head; the MTP layer is of the last
layer's kinds (MLA + routed experts).  `departures`: rotate-half on the 64
rotary channels where DeepSeek's checkpoints store them interleaved (a
permutation of W_qb's and W_kva's rows); the correction bias's balancing
update is not in config.json and the bias is a constant here.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

HIGHEST = "highest"


def rms_norm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gain


# ---------------------------------------------------------------------------
# rotary positions under YaRN, DeepSeek's convention
# ---------------------------------------------------------------------------
def yarn_inv_freq(cfg):
    """Inverse frequencies of the rotary channels: theta^(-2i/d) for the
    highest ones, divided by `factor` for the lowest, a linear ramp between
    the dimensions that turn `beta_fast` and `beta_slow` times over
    `original_max_position_embeddings`."""
    d, theta = cfg["qk_rope_head_dim"], float(cfg["rope_theta"])
    rs = cfg["rope_scaling"]
    assert rs["type"] == "yarn"
    factor, orig = float(rs["factor"]), rs["original_max_position_embeddings"]

    def dim_of(turns):
        return d * math.log(orig / (turns * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(dim_of(rs["beta_fast"])), 0)
    high = min(math.ceil(dim_of(rs["beta_slow"])), d - 1)
    if low == high:
        high += 0.001
    i = jnp.arange(d // 2, dtype=jnp.float32)
    extra = theta ** (-2.0 * i / d)                   # unscaled
    ramp = jnp.clip((i - low) / (high - low), 0.0, 1.0)
    return extra / factor * ramp + extra * (1.0 - ramp)


def mscale(factor, m):
    return 0.1 * m * math.log(factor) + 1.0 if factor > 1 else 1.0


def softmax_scale(cfg):
    rs = cfg["rope_scaling"]
    s = mscale(rs["factor"], rs["mscale_all_dim"])
    return (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5 * s * s


def rotate(x, positions, cfg):
    """Rotate-half rotary positions on x (..., T, heads, d) at `positions` (T,)."""
    rs = cfg["rope_scaling"]
    angle = positions[:, None].astype(jnp.float32) * yarn_inv_freq(cfg)[None, :]
    cos = jnp.cos(angle)[:, None, :] * (mscale(rs["factor"], rs["mscale"])
                                        / mscale(rs["factor"], rs["mscale_all_dim"]))
    sin = jnp.sin(angle)[:, None, :] * (mscale(rs["factor"], rs["mscale"])
                                        / mscale(rs["factor"], rs["mscale_all_dim"]))
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


# ---------------------------------------------------------------------------
# the mHC residual path
# ---------------------------------------------------------------------------
def sinkhorn_knopp(logits, iters, eps):
    """Rows then columns, `iters` times each; the first row normalisation is
    a softmax, every later denominator the sum + eps."""
    m = jnp.exp(logits - jnp.max(logits, axis=-1, keepdims=True))
    m = m / jnp.sum(m, axis=-1, keepdims=True)
    m = m / (jnp.sum(m, axis=-2, keepdims=True) + eps)
    for _ in range(iters - 1):
        m = m / (jnp.sum(m, axis=-1, keepdims=True) + eps)
        m = m / (jnp.sum(m, axis=-2, keepdims=True) + eps)
    return m


def hc_mixes(x, hp, cfg):
    """(H_pre (..., n), H_post (..., n), H_res (..., n, n)) of streams x (..., n, U)."""
    n = cfg["hc_mult"]
    flat = x.reshape(x.shape[:-2] + (-1,))
    z = rms_norm(flat, hp["gain"], cfg["rms_norm_eps"]) @ hp["phi"]
    a, b = hp["alpha"], hp["bias"]
    pre = jax.nn.sigmoid(a[0] * z[..., :n] + b[:n])
    post = 2.0 * jax.nn.sigmoid(a[1] * z[..., n:2 * n] + b[n:2 * n])
    res = jnp.clip(a[2] * z[..., 2 * n:] + b[2 * n:], cfg["mhc_h_res_clamp_min"],
                   cfg["mhc_h_res_clamp_max"])
    res = sinkhorn_knopp(res.reshape(res.shape[:-1] + (n, n)), cfg["hc_sinkhorn_iters"],
                         cfg["hc_eps"])
    return pre, post, res


def sublayer(x, hp, f, cfg):
    """X <- H_res X + H_post^T f(H_pre X)."""
    pre, post, res = hc_mixes(x, hp, cfg)
    u = jnp.einsum("...i,...iu->...u", pre, x)
    return jnp.einsum("...ij,...ju->...iu", res, x) + post[..., None] * f(u)[..., None, :]


# ---------------------------------------------------------------------------
# attention and the FFN
# ---------------------------------------------------------------------------
def mla(u, lp, cfg, q_positions=None):
    """Latent attention's output for the sublayer input u (B, T, U).
    `q_positions` evaluates only those query rows (keys and values always
    cover the whole sequence), so a long sequence can be checked in blocks."""
    nh, dn, dr = cfg["num_attention_heads"], cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    dv, rank, eps = cfg["v_head_dim"], cfg["kv_lora_rank"], cfg["rms_norm_eps"]
    b, t, _ = u.shape
    x = rms_norm(u, lp["norm1"], eps)
    pos = jnp.arange(t)
    qpos = pos if q_positions is None else q_positions
    q = (rms_norm(x[:, qpos] @ lp["wqa"], lp["q_norm"], eps) @ lp["wqb"]).reshape(
        b, len(qpos), nh, dn + dr)
    q = jnp.concatenate([q[..., :dn], rotate(q[..., dn:], qpos, cfg)], axis=-1)
    down = x @ lp["wkva"]
    c, k_r = down[..., :rank], rotate(down[:, :, None, rank:], pos, cfg)
    kv = (rms_norm(c, lp["kv_norm"], eps) @ lp["wkvb"]).reshape(b, t, nh, dn + dv)
    k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(k_r, (b, t, nh, dr))], axis=-1)
    v = kv[..., dn:]
    s = jnp.einsum("bthd,bshd->bhts", q, k) * softmax_scale(cfg)
    seen = qpos[:, None] >= pos[None, :]
    p = jax.nn.softmax(jnp.where(seen[None, None], s, -jnp.inf), axis=-1)
    return jnp.einsum("bhts,bshd->bthd", p, v).reshape(b, len(qpos), nh * dv) @ lp["wo"]


def mla_in_blocks(u, lp, cfg, block):
    """`mla` one block of query rows after another, each recomputed in the
    backward pass (`lax.map`: a Python loop's blocks are independent, and
    XLA then holds many of them at once)."""
    b, t, width = u.shape
    if block is None or block >= t:
        return mla(u, lp, cfg)
    # a last block past the end repeats row T-1, and the repeats are dropped
    rows = jax.checkpoint(lambda start: mla(
        u, lp, cfg, jnp.minimum(start + jnp.arange(block), t - 1)))
    out = jax.lax.map(rows, jnp.arange(0, t, block))         # (blocks, B, block, U)
    return jnp.moveaxis(out, 0, 1).reshape(b, -1, width)[:, :t]


def swiglu(m, gate, up, down):
    return (jax.nn.silu(m @ gate) * (m @ up)) @ down


def route(m, router, bias, cfg):
    """(experts picked (..., k), their weights (..., k), the scores over all
    experts)."""
    assert cfg["scoring_func"] == "sigmoid" and cfg["topk_method"] == "noaux_tc"
    assert cfg["n_group"] == 1 and cfg["topk_group"] == 1
    s = jax.nn.sigmoid(m @ router)
    _, top_e = jax.lax.top_k(s + jax.lax.stop_gradient(bias), cfg["num_experts_per_tok"])
    return top_e, weights_of(s, top_e, cfg), s


def weights_of(s, top_e, cfg):
    w = jnp.take_along_axis(s, top_e, axis=-1)
    if cfg["norm_topk_prob"]:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return w * cfg["routed_scaling_factor"]


def ffn(u, lp, cfg, experts_held, ep_rank, picks=None):
    """The FFN's output for the sublayer input u: a dense SwiGLU where the
    layer has one, else the held experts' part of Routed(m) plus Shared(m)
    whole.  `picks` (experts (..., k)) overrides WHICH experts the router
    chose, their weights still this router's scores."""
    m = rms_norm(u, lp["norm2"], cfg["rms_norm_eps"])
    if "router" not in lp:
        return swiglu(m, lp["gate"], lp["up"], lp["down"])
    top_e, top_w, s = route(m, lp["router"], lp["bias"], cfg)
    if picks is not None:
        top_e, top_w = picks, weights_of(s, picks, cfg)
    y = swiglu(m, lp["shared_gate"], lp["shared_up"], lp["shared_down"])
    for j in range(experts_held):          # a plain loop over the experts held
        e = ep_rank * experts_held + j
        w_e = jnp.sum(jnp.where(top_e == e, top_w, 0.0), axis=-1)   # 0 where not picked
        y = y + w_e[..., None] * swiglu(m, lp["gate"][j], lp["up"][j], lp["down"][j])
    return y


def layer(x, lp, cfg, held, ep_rank, picks=None, block=None):
    """One decoder layer on the streams x (B, T, n, U)."""
    x = sublayer(x, lp["hc_attn"], lambda u: mla_in_blocks(u, lp, cfg, block), cfg)
    return sublayer(x, lp["hc_ffn"], lambda u: ffn(u, lp, cfg, held, ep_rank, picks), cfg)


def layers_held(cfg):
    return list(cfg.get("layers_held", range(cfg["num_hidden_layers"])))


def layer_kinds(cfg):
    """The FFN kind of each layer held, in order."""
    return ["dense" if l < cfg["first_k_dense_replace"] else "sparse" for l in layers_held(cfg)]


def _run_layer(x, lp, cfg, held, ep_rank, pk, block):
    if block is None:
        return layer(x, lp, cfg, held, ep_rank, pk)
    # each layer recomputed in the backward pass: a layer's streams kept, not its insides
    return jax.checkpoint(lambda x, lp, pk: layer(x, lp, cfg, held, ep_rank, pk, block))(
        x, lp, pk)


def hidden(params, ids, cfg, experts_held=None, ep_rank=0, picks=None, block=None):
    """(the main model's final state before its norm, summed over the
    streams (B, T, U); the embedding (B, T, U)).  `picks[l]` is None for a
    dense layer; with `block` attention is evaluated `block` query positions
    at a time and every layer recomputed in the backward pass (the same
    mathematics, at the published widths on one chip)."""
    held = cfg["n_routed_experts"] if experts_held is None else experts_held
    e = params["embed"][ids]
    x = jnp.broadcast_to(e[:, :, None, :], e.shape[:2] + (cfg["hc_mult"],) + e.shape[2:])
    for l, lp in enumerate(params["layers"]):
        pk = None if picks is None else picks[l]
        x = _run_layer(x, lp, cfg, held, ep_rank, pk, block)
    return jnp.sum(x, axis=2), e


def mtp_hidden(params, h, e, cfg, experts_held=None, ep_rank=0, picks=None, block=None):
    """The MTP module's normed output for positions 0..T-2 (B, T-1, U)."""
    held = cfg["n_routed_experts"] if experts_held is None else experts_held
    mp, eps = params["mtp"], cfg["rms_norm_eps"]
    t = h.shape[1] - 1
    z = jnp.concatenate([rms_norm(h[:, :t], mp["hnorm"], eps),
                         rms_norm(e[:, 1:], mp["enorm"], eps)], axis=-1) @ mp["eh"]
    x = jnp.broadcast_to(z[:, :, None, :], z.shape[:2] + (cfg["hc_mult"],) + z.shape[2:])
    pk = None if picks is None else picks[len(params["layers"])][:, :t]
    x = _run_layer(x, mp["layer"], cfg, held, ep_rank, pk, block)
    return rms_norm(jnp.sum(x, axis=2), mp["norm"], eps)


def logits(params, ids, cfg, **share):
    """(the main head's logits (B, T, V), the MTP head's (B, T-1, V))."""
    with jax.default_matmul_precision(HIGHEST):
        h, e = hidden(params, ids, cfg, **share)
        main = rms_norm(h, params["norm"], cfg["rms_norm_eps"]) @ params["head"]
        return main, mtp_hidden(params, h, e, cfg, **share) @ params["head"]


def cross_entropy(lg, targets):
    logp = jax.nn.log_softmax(lg, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, targets[..., None], axis=-1))


def losses(params, ids, cfg, **share):
    """(CE_main: position t predicts ids[:, t+1]; CE_mtp: position t
    predicts ids[:, t+2], t = 0..T-3)."""
    main, ahead = logits(params, ids, cfg, **share)
    return cross_entropy(main[:, :-1], ids[:, 1:]), cross_entropy(ahead[:, :-1], ids[:, 2:])


def loss(params, ids, cfg, **share):
    """CE_main + lambda CE_mtp."""
    main, extra = losses(params, ids, cfg, **share)
    return main + cfg["mtp_loss_weight"] * extra


def loss_and_grads(params, ids, cfg, **share):
    return jax.value_and_grad(lambda p: loss(p, ids, cfg, **share))(params)


def init_params(key, cfg, experts_held=None, vocab=None, std=0.02):
    """Normal(std) matrices, embedding and phi; ones for the norm gains;
    alpha 0.01; zero biases."""
    u, e, f = cfg["hidden_size"], cfg["n_routed_experts"], cfg["moe_intermediate_size"]
    n, nh = cfg["hc_mult"], cfg["num_attention_heads"]
    dn, dr, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    rq, rkv = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    held = e if experts_held is None else experts_held
    v = cfg["vocab_size"] if vocab is None else vocab
    keys = iter(jax.random.split(key, 3 + 24 * (len(layers_held(cfg)) + 1)))

    def normal(*shape):
        return std * jax.random.normal(next(keys), shape, jnp.float32)

    def hc():
        return {"gain": jnp.ones((n * u,)), "phi": normal(n * u, 2 * n + n * n),
                "alpha": jnp.full((3,), 0.01), "bias": jnp.zeros((2 * n + n * n,))}

    def one_layer(kind):
        lp = {"hc_attn": hc(), "hc_ffn": hc(), "norm1": jnp.ones((u,)), "norm2": jnp.ones((u,)),
              "wqa": normal(u, rq), "q_norm": jnp.ones((rq,)), "wqb": normal(rq, nh * (dn + dr)),
              "wkva": normal(u, rkv + dr), "kv_norm": jnp.ones((rkv,)),
              "wkvb": normal(rkv, nh * (dn + dv)), "wo": normal(nh * dv, u)}
        if kind == "dense":
            i = cfg["intermediate_size"]
            lp.update(gate=normal(u, i), up=normal(u, i), down=normal(i, u))
        else:
            fs = f * cfg["n_shared_experts"]
            lp.update(router=normal(u, e), bias=jnp.zeros((e,)), gate=normal(held, u, f),
                      up=normal(held, u, f), down=normal(held, f, u),
                      shared_gate=normal(u, fs), shared_up=normal(u, fs),
                      shared_down=normal(fs, u))
        return lp

    kinds = layer_kinds(cfg)
    return {"embed": normal(v, u), "norm": jnp.ones((u,)), "head": normal(u, v),
            "layers": [one_layer(k) for k in kinds],
            "mtp": {"hnorm": jnp.ones((u,)), "enorm": jnp.ones((u,)), "eh": normal(2 * u, u),
                    "layer": one_layer(kinds[-1]), "norm": jnp.ones((u,))}}
