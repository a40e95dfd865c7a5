"""Mellum2-12B-A2.5B-Instruct, as its config.json describes it, in plain
`jax.numpy` float32: forward, loss and (by `jax.grad`) gradients.

Written from the layer equations (ISSUE 32; the catalog row of
https://huggingface.co/JetBrains/Mellum2-12B-A2.5B-Instruct/blob/main/config.json),
not from the system's code: dense masked attention, a Python loop over the
experts, no kernel, no sort, no cache.  Every matmul runs under
`jax.default_matmul_precision("highest")` (on a TPU an f32 matmul is
otherwise computed in bf16 passes).

One chip's share of a deployment is a parameter here exactly as in the
system: `experts_held` contiguous experts from `ep_rank * experts_held`
(the router still scores all `num_experts` and picks `top_k` of them; what
the absent experts would add is left out), and a vocabulary slice (the
embedding and the head simply have that many rows).  With
`experts_held == num_experts` this is the published layer.

    params = {"embed": (V, U), "layers": [{"norm1": (U,), "wq": (U, H*D),
              "wk": (U, Hkv*D), "wv": (U, Hkv*D), "wo": (H*D, U),
              "norm2": (U,), "router": (U, E), "gate": (held, U, F),
              "up": (held, U, F), "down": (held, F, U)}, ...],
              "norm": (U,), "head": (U, V)}

`assumed` (the config has no key for them): no per-head normalisation of q
or k; no auxiliary router loss.  `departures`: the multi-token-prediction
head that the model card mentions has no key in the config and is left out.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

HIGHEST = "highest"


def rope_inv_freq(head_dim, rope):
    """(inverse frequencies (head_dim/2,), factor on cos and sin) of one
    `rope_parameters` entry: plain, or YaRN (Peng et al. 2023) as
    transformers' `_compute_yarn_parameters` defines it."""
    theta = float(rope["rope_theta"])
    i = jnp.arange(0, head_dim, 2, dtype=jnp.float32)
    f = theta ** (-i / head_dim)
    if rope.get("rope_type", "default") != "yarn":
        return f, 1.0
    factor, orig = float(rope["factor"]), rope["original_max_position_embeddings"]

    def dim_of(rotations):   # the dimension that turns `rotations` times over `orig`
        return head_dim * math.log(orig / (rotations * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(dim_of(rope["beta_fast"])), 0)
    high = min(math.ceil(dim_of(rope["beta_slow"])), head_dim - 1)
    ramp = jnp.clip((jnp.arange(head_dim // 2, dtype=jnp.float32) - low)
                    / max(high - low, 0.001), 0.0, 1.0)
    inv_freq = (f / factor) * ramp + f * (1.0 - ramp)
    scale = rope.get("attention_factor")
    if scale is None:
        scale = 0.1 * math.log(factor) + 1.0
    return inv_freq, float(scale)


def rope_tables(positions, head_dim, rope):
    """cos, sin of shape (T, head_dim) for rotate-half rotary positions."""
    inv_freq, scale = rope_inv_freq(head_dim, rope)
    angle = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    angle = jnp.concatenate([angle, angle], axis=-1)
    return jnp.cos(angle) * scale, jnp.sin(angle) * scale


def rotate(x, cos, sin):
    """x (B, T, heads, D): x*cos + rotate_half(x)*sin."""
    half = x.shape[-1] // 2
    turned = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * cos[None, :, None, :] + turned * sin[None, :, None, :]


def rms_norm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gain


def attention(h, lp, cfg, kind, q_positions=None):
    """One attention sub-layer's addend.  `q_positions` (a 1-D index array)
    evaluates only those query rows — keys and values always cover the whole
    sequence — so a long sequence can be checked in blocks of positions."""
    b, t, _ = h.shape
    nh, nkv, d = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    a = rms_norm(h, lp["norm1"], cfg["rms_norm_eps"])
    pos = jnp.arange(t)
    qpos = pos if q_positions is None else q_positions
    cos, sin = rope_tables(pos, d, cfg["rope_parameters"][kind])
    q = (a[:, qpos] @ lp["wq"]).reshape(b, len(qpos), nh, d)
    k = (a @ lp["wk"]).reshape(b, t, nkv, d)
    v = (a @ lp["wv"]).reshape(b, t, nkv, d)
    q = rotate(q, cos[qpos], sin[qpos])
    k = rotate(k, cos, sin)
    group = nh // nkv
    k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    s = jnp.einsum("bthd,bshd->bhts", q, k) / math.sqrt(d)
    dist = qpos[:, None] - pos[None, :]
    seen = dist >= 0
    if kind == "sliding_attention":
        seen = seen & (dist < cfg["sliding_window"])
    p = jax.nn.softmax(jnp.where(seen[None, None], s, -jnp.inf), axis=-1)
    o = jnp.einsum("bhts,bshd->bthd", p, v).reshape(b, len(qpos), nh * d)
    return o @ lp["wo"]


def route(m, router, top_k):
    """(experts picked (..., k), their renormalised weights (..., k), the
    softmax over all experts) for normed activations m."""
    p = jax.nn.softmax(m @ router, axis=-1)
    top_p, top_e = jax.lax.top_k(p, top_k)
    return top_e, top_p / jnp.sum(top_p, axis=-1, keepdims=True), p


def experts(h, lp, cfg, experts_held, ep_rank, picks=None):
    """The held experts' part of the routed layer's addend.  `picks` (experts
    (..., k)) overrides WHICH experts the router chose — their weights are
    still this router's probabilities, renormalised — to compare with a system
    whose choice differs at a near-tie."""
    m = rms_norm(h, lp["norm2"], cfg["rms_norm_eps"])
    top_e, top_w, p = route(m, lp["router"], cfg["num_experts_per_tok"])
    if picks is not None:
        top_e = picks
        top_p = jnp.take_along_axis(p, top_e, axis=-1)
        top_w = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    y = jnp.zeros_like(h)
    for j in range(experts_held):          # a plain loop over the experts held
        e = ep_rank * experts_held + j
        w_e = jnp.sum(jnp.where(top_e == e, top_w, 0.0), axis=-1)   # 0 where not picked
        out = (jax.nn.silu(m @ lp["gate"][j]) * (m @ lp["up"][j])) @ lp["down"][j]
        y = y + w_e[..., None] * out
    return y


def hidden(params, ids, cfg, experts_held=None, ep_rank=0, picks=None,
           block=None):
    """Final hidden states (B, T, U) before the last norm.  With `block`
    each attention is evaluated `block` query positions at a time and every
    sub-layer is recomputed in the backward pass (`jax.checkpoint`), so that
    T = 8192 at the published widths fits one chip: the same mathematics."""
    held = cfg["num_experts"] if experts_held is None else experts_held
    h = params["embed"][ids]
    t = ids.shape[1]
    for l, lp in enumerate(params["layers"]):
        kind = cfg["layer_types"][l]
        if block is None:
            h = h + attention(h, lp, cfg, kind)
            h = h + experts(h, lp, cfg, held, ep_rank,
                            None if picks is None else picks[l])
            continue
        rows = jax.checkpoint(
            lambda h, lp, qpos, kind=kind: attention(h, lp, cfg, kind, qpos))
        h = h + jnp.concatenate(
            [rows(h, lp, jnp.arange(s, min(s + block, t)))
             for s in range(0, t, block)], axis=1)
        h = h + jax.checkpoint(
            lambda h, lp, pk: experts(h, lp, cfg, held, ep_rank, pk))(
                h, lp, None if picks is None else picks[l])
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
    """Normal(std) matrices and embedding, ones for the norm gains."""
    u, d = cfg["hidden_size"], cfg["head_dim"]
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    e, f = cfg["num_experts"], cfg["moe_intermediate_size"]
    held = e if experts_held is None else experts_held
    v = cfg["vocab_size"] if vocab is None else vocab
    keys = iter(jax.random.split(key, 2 + 8 * cfg["num_hidden_layers"]))

    def normal(*shape):
        return std * jax.random.normal(next(keys), shape, jnp.float32)

    layers = [{"norm1": jnp.ones((u,)), "wq": normal(u, nh * d),
               "wk": normal(u, nkv * d), "wv": normal(u, nkv * d),
               "wo": normal(nh * d, u), "norm2": jnp.ones((u,)),
               "router": normal(u, e), "gate": normal(held, u, f),
               "up": normal(held, u, f), "down": normal(held, f, u)}
              for _ in range(cfg["num_hidden_layers"])]
    return {"embed": normal(v, u), "layers": layers, "norm": jnp.ones((u,)),
            "head": normal(u, v)}
