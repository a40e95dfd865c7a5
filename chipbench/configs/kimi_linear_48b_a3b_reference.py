"""Kimi-Linear-48B-A3B-Instruct, as its config.json and the Kimi Linear
report (arXiv:2510.26692) describe it, in plain `jax.numpy` float32:
forward, loss and (by `jax.grad`) gradients.

Written from the layer equations (ISSUE 34; the catalog row of
https://huggingface.co/moonshotai/Kimi-Linear-48B-A3B-Instruct/blob/main/config.json;
the public implementation is `fla/layers/kda.py` with the model's own
`modeling_kimi.py`), not from the system's code: Kimi Delta Attention as the
literal token-by-token recurrence (a `lax.scan` over TOKENS: no chunk, no
triangular solve), the short convolution as four shifted adds, latent
attention as dense masked attention, the router and experts as a Python loop
over the experts held, no kernel, no sort, no cache.  Every matmul runs under
`jax.default_matmul_precision("highest")` (on a TPU an f32 matmul is
otherwise computed in bf16 passes).

Layers are 1-indexed as published: layer l's attention is KDA for l in
`linear_attn_config.kda_layers`, MLA for l in `full_attn_layers`; its FFN is
a dense SwiGLU for l <= `first_k_dense_replace`, else routed experts plus
`num_shared_experts` shared SwiGLU expert(s) of the experts' width.

One chip's share of a deployment is a parameter here exactly as in the
system: `experts_held` contiguous experts from `ep_rank * experts_held` (the
router still scores all `num_experts` and picks `num_experts_per_token`; what
the absent experts would add is left out; the shared expert is whole), and a
vocabulary slice (the embedding and the head simply have that many rows).
With `experts_held == num_experts` this is the published layer.

    params = {"embed": (V, U), "norm": (U,), "head": (U, V), "layers": [
      {"norm1": (U,), "norm2": (U,),
       # KDA (H heads of D; R the gates' low rank)
       "wq" "wk" "wv": (U, H*D), "conv_q" "conv_k" "conv_v": (H*D, taps),
       "wf_a": (U, R), "wf_b": (R, H*D), "A_log": (H,), "dt_bias": (H*D,),
       "wb": (U, H), "wg_a": (U, R), "wg_b": (R, H*D), "bg": (H*D,),
       "o_norm": (D,), "wo": (H*D, U),
       # or MLA
       "wq": (U, H*(dn+dr)), "wkva": (U, rank+dr), "kv_norm": (rank,),
       "wkvb": (rank, H*(dn+dv)), "wo": (H*dv, U),
       # dense FFN
       "gate" "up": (U, F), "down": (F, U),
       # or routed + shared
       "router": (U, E), "bias": (E,), "gate" "up": (held, U, F),
       "down": (held, F, U), "shared_gate" "shared_up": (U, F),
       "shared_down": (F, U)}, ...]}

`assumed` (the config has no key for them; the convention of
`fla/layers/kda.py`): the low-rank width of both gates is the head size 128;
the output gate's up-projection has a bias, the decay gate's none; SiLU after
each convolution; q is scaled by d_k^-1/2 after its L2 norm; the L2 norm is
x * rsqrt(sum x^2 + 1e-6); the head norm's eps is `rms_norm_eps`; no
auxiliary router loss.  `departures`: the correction bias's balancing
update is not in config.json and the bias is a constant here (zeros at
initialisation); `head_dim` 72 (hidden / heads) is used by neither layer.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = "highest"
WALK = 128      # tokens the recurrence walks between two kept states, at most


def rms_norm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gain


def short_conv(x, w):
    """Depthwise causal convolution, x (B, T, Ch), w (Ch, taps): four shifted
    adds; out[t] = sum_j w[:, j] * x[t - (taps - 1) + j], zeros before t = 0."""
    taps, t = w.shape[1], x.shape[1]
    out = jnp.zeros_like(x)
    for j in range(taps):
        back = taps - 1 - j                       # how far this tap looks back
        shifted = jnp.pad(x, ((0, 0), (back, 0), (0, 0)))[:, :t]
        out = out + shifted * w[:, j]
    return out


def delta_rule(q, k, v, g, beta, block=None):
    """The recurrence itself, one token at a time.  q, k, g (B, T, H, K),
    v (B, T, H, V), beta (B, T, H); state S (B, H, K, V), zero before t = 0:

        S_t = (I - beta_t k_t k_t^T) Diag(exp g_t) S_(t-1) + beta_t k_t v_t^T
        o_t = S_t^T q_t

    `block` walks the tokens in blocks whose inner steps are recomputed in the
    backward pass (`jax.checkpoint`), so that a state per TOKEN is never kept:
    the same mathematics, for T = 16,384 at the published widths."""
    b, t, h, kd = q.shape

    def token(state, x):
        q_t, k_t, v_t, g_t, b_t = x
        state = jnp.exp(g_t)[..., None] * state                     # Diag(alpha) S
        u = b_t[..., None] * (v_t - jnp.einsum("bhk,bhkv->bhv", k_t, state))
        state = state + k_t[..., None] * u[..., None, :]
        return state, jnp.einsum("bhk,bhkv->bhv", q_t, state)

    xs = tuple(jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, beta))    # time first
    state = jnp.zeros((b, h, kd, v.shape[-1]), jnp.float32)
    if block is None or block >= t:
        _, o = jax.lax.scan(token, state, xs)
    else:
        assert t % block == 0
        walk = jax.checkpoint(lambda s, x: jax.lax.scan(token, s, x))
        _, o = jax.lax.scan(walk, state, tuple(
            x.reshape((t // block, block) + x.shape[1:]) for x in xs))
        o = o.reshape((t,) + o.shape[2:])
    return jnp.moveaxis(o, 0, 1)


def kda(h, lp, cfg, block=None):
    """Kimi Delta Attention's addend for the residual stream h (B, T, U)."""
    lin = cfg["linear_attn_config"]
    nh, d = lin["num_heads"], lin["head_dim"]
    b, t, _ = h.shape
    x = rms_norm(h, lp["norm1"], cfg["rms_norm_eps"])

    def heads(w, conv):
        return jax.nn.silu(short_conv(x @ w, conv)).reshape(b, t, nh, d)

    def l2(z):                                     # assumed: fla's l2norm, eps 1e-6
        return z * jax.lax.rsqrt(jnp.sum(z * z, axis=-1, keepdims=True) + 1e-6)

    q = l2(heads(lp["wq"], lp["conv_q"])) * d ** -0.5
    k = l2(heads(lp["wk"], lp["conv_k"]))
    v = heads(lp["wv"], lp["conv_v"])
    g = -jnp.exp(lp["A_log"])[None, None, :, None] * jax.nn.softplus(
        (x @ lp["wf_a"]) @ lp["wf_b"] + lp["dt_bias"]).reshape(b, t, nh, d)
    beta = jax.nn.sigmoid(x @ lp["wb"])
    o = delta_rule(q, k, v, g, beta, block)
    o = rms_norm(o, lp["o_norm"], cfg["rms_norm_eps"])
    gate = jax.nn.sigmoid((x @ lp["wg_a"]) @ lp["wg_b"] + lp["bg"])
    return (o.reshape(b, t, nh * d) * gate) @ lp["wo"]


def mla(h, lp, cfg, q_positions=None):
    """Latent attention's addend, NoPE (`mla_use_nope`: the 64 "rope" channels
    of q and the shared k_rot are used as they are, never rotated).
    `q_positions` evaluates only those query rows (keys and values always
    cover the whole sequence), so a long sequence can be checked in blocks."""
    nh, dn, dr = cfg["num_attention_heads"], cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    dv, rank = cfg["v_head_dim"], cfg["kv_lora_rank"]
    b, t, _ = h.shape
    x = rms_norm(h, lp["norm1"], cfg["rms_norm_eps"])
    pos = jnp.arange(t)
    qpos = pos if q_positions is None else q_positions
    q = (x[:, qpos] @ lp["wq"]).reshape(b, len(qpos), nh, dn + dr)
    down = x @ lp["wkva"]
    c, k_rot = down[..., :rank], down[..., rank:]
    kv = (rms_norm(c, lp["kv_norm"], cfg["rms_norm_eps"]) @ lp["wkvb"]).reshape(b, t, nh, dn + dv)
    k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(k_rot[:, :, None, :], (b, t, nh, dr))],
                        axis=-1)
    v = kv[..., dn:]
    s = jnp.einsum("bthd,bshd->bhts", q, k) * (dn + dr) ** -0.5
    seen = qpos[:, None] >= pos[None, :]
    p = jax.nn.softmax(jnp.where(seen[None, None], s, -jnp.inf), axis=-1)
    return jnp.einsum("bhts,bshd->bthd", p, v).reshape(b, len(qpos), nh * dv) @ lp["wo"]


def swiglu(m, gate, up, down):
    return (jax.nn.silu(m @ gate) * (m @ up)) @ down


def route(m, router, bias, cfg):
    """(experts picked (..., k), their weights (..., k), the scores over all
    experts): sigmoid scores, the k largest of score + bias (one group:
    `num_expert_group` = `topk_group` = 1), weights from the unbiased scores,
    renormalised (`moe_renormalize`) and scaled by `routed_scaling_factor`."""
    assert cfg["moe_router_activation_func"] == "sigmoid" and cfg["num_expert_group"] == 1
    s = jax.nn.sigmoid(m @ router)
    _, top_e = jax.lax.top_k(s + jax.lax.stop_gradient(bias), cfg["num_experts_per_token"])
    return top_e, weights_of(s, top_e, cfg), s


def weights_of(s, top_e, cfg):
    w = jnp.take_along_axis(s, top_e, axis=-1)
    if cfg["moe_renormalize"]:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return w * cfg["routed_scaling_factor"]


def ffn(h, lp, cfg, experts_held, ep_rank, picks=None):
    """The FFN's addend: a dense SwiGLU where the layer has one, else the held
    experts' part of Routed(x) plus Shared(x) whole.  `picks` (experts
    (..., k)) overrides WHICH experts the router chose, their weights still
    this router's scores, to compare with a system whose choice differs at a
    near-tie."""
    m = rms_norm(h, lp["norm2"], cfg["rms_norm_eps"])
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


def layer_kinds(cfg):
    """[(attention, ffn)] of the first `num_hidden_layers` published layers."""
    lin = cfg["linear_attn_config"]
    kinds = []
    for l in range(1, cfg["num_hidden_layers"] + 1):
        assert (l in lin["kda_layers"]) != (l in lin["full_attn_layers"]), l
        kinds.append(("kda" if l in lin["kda_layers"] else "mla",
                      "dense" if l <= cfg["first_k_dense_replace"] else "sparse"))
    return kinds


def hidden(params, ids, cfg, experts_held=None, ep_rank=0, picks=None, block=None):
    """Final hidden states (B, T, U) before the last norm.  With `block` MLA is
    evaluated `block` query positions at a time, the recurrence walks blocks of
    min(`block`, `WALK`) tokens, and every sub-layer is recomputed in the backward pass
    (`jax.checkpoint`), so that T = 16,384 at the published widths fits one
    chip: the same mathematics.  `picks[l]` is None for a dense layer."""
    held = cfg["num_experts"] if experts_held is None else experts_held
    h = params["embed"][ids]
    t = ids.shape[1]
    for l, ((attn, _ffn), lp) in enumerate(zip(layer_kinds(cfg), params["layers"])):
        pk = None if picks is None else picks[l]
        if block is None:
            h = h + (kda(h, lp, cfg) if attn == "kda" else mla(h, lp, cfg))
            h = h + ffn(h, lp, cfg, held, ep_rank, pk)
            continue
        if attn == "kda":    # a state a token is kept inside a block: 128 of 2 MB at most
            h = h + jax.checkpoint(lambda h, lp: kda(h, lp, cfg, min(block, WALK)))(h, lp)
        else:     # one block of query rows after another (`lax.map`: a Python loop's
            # blocks are independent, and XLA then holds many of them at once)
            assert t % block == 0
            rows = jax.checkpoint(
                lambda start, h=h, lp=lp: mla(h, lp, cfg, start + jnp.arange(block)))
            out = jax.lax.map(rows, jnp.arange(0, t, block))          # (T/block, B, block, U)
            h = h + jnp.moveaxis(out, 0, 1).reshape(h.shape)
        h = h + jax.checkpoint(lambda h, lp, pk: ffn(h, lp, cfg, held, ep_rank, pk))(h, lp, pk)
    return h


def logits(params, ids, cfg, **share):
    with jax.default_matmul_precision(HIGHEST):
        h = hidden(params, ids, cfg, **share)
        return rms_norm(h, params["norm"], cfg["rms_norm_eps"]) @ params["head"]


def loss(params, ids, cfg, **share):
    """Mean next-token cross-entropy: position t predicts ids[:, t+1]."""
    lg = logits(params, ids, cfg, **share)[:, :-1]
    logp = jax.nn.log_softmax(lg, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, ids[:, 1:, None], axis=-1))


def loss_and_grads(params, ids, cfg, **share):
    return jax.value_and_grad(lambda p: loss(p, ids, cfg, **share))(params)


def init_params(key, cfg, experts_held=None, vocab=None, std=0.02):
    """Normal(std) matrices, convolutions and embedding; ones for the norm
    gains; A_log = log U(1, 16) and dt_bias = softplus^-1 of a log-uniform
    step in [1e-3, 1e-1] (`fla/layers/kda.py`); zero biases."""
    u, e, f = cfg["hidden_size"], cfg["num_experts"], cfg["moe_intermediate_size"]
    lin = cfg["linear_attn_config"]
    nh, d, taps = lin["num_heads"], lin["head_dim"], lin["short_conv_kernel_size"]
    r = d                                          # assumed: the gates' low rank is the head size
    ah, dn, dr = cfg["num_attention_heads"], cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    dv, rank = cfg["v_head_dim"], cfg["kv_lora_rank"]
    held = e if experts_held is None else experts_held
    v = cfg["vocab_size"] if vocab is None else vocab
    keys = iter(jax.random.split(key, 2 + 24 * cfg["num_hidden_layers"]))

    def normal(*shape):
        return std * jax.random.normal(next(keys), shape, jnp.float32)

    layers = []
    for attn, kind in layer_kinds(cfg):
        lp = {"norm1": jnp.ones((u,)), "norm2": jnp.ones((u,))}
        if attn == "kda":
            dt = jnp.exp(jax.random.uniform(next(keys), (nh * d,), jnp.float32,
                                            jnp.log(1e-3), jnp.log(1e-1)))
            lp.update(
                wq=normal(u, nh * d), wk=normal(u, nh * d), wv=normal(u, nh * d),
                conv_q=normal(nh * d, taps), conv_k=normal(nh * d, taps),
                conv_v=normal(nh * d, taps), wf_a=normal(u, r), wf_b=normal(r, nh * d),
                A_log=jnp.log(jax.random.uniform(next(keys), (nh,), jnp.float32, 1.0, 16.0)),
                dt_bias=dt + jnp.log(-jnp.expm1(-dt)), wb=normal(u, nh),
                wg_a=normal(u, r), wg_b=normal(r, nh * d), bg=jnp.zeros((nh * d,)),
                o_norm=jnp.ones((d,)), wo=normal(nh * d, u))
        else:
            lp.update(wq=normal(u, ah * (dn + dr)), wkva=normal(u, rank + dr),
                      kv_norm=jnp.ones((rank,)), wkvb=normal(rank, ah * (dn + dv)),
                      wo=normal(ah * dv, u))
        if kind == "dense":
            i = cfg["intermediate_size"]
            lp.update(gate=normal(u, i), up=normal(u, i), down=normal(i, u))
        else:
            lp.update(router=normal(u, e), bias=jnp.zeros((e,)), gate=normal(held, u, f),
                      up=normal(held, u, f), down=normal(held, f, u),
                      shared_gate=normal(u, f), shared_up=normal(u, f),
                      shared_down=normal(f, u))
        layers.append(lp)
    return {"embed": normal(v, u), "layers": layers, "norm": jnp.ones((u,)),
            "head": normal(u, v)}
