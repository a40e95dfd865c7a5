"""Decoder language models: pre-norm blocks of grouped-query causal
attention (a sliding window in some layers, rotary positions of a kind
per layer type) and sparse routed SwiGLU experts.

The block set `models/transformer.py` (a post-norm encoder) lacks:
`RMSNorm`, rotary tables with plain and YaRN-scaled frequencies,
`GroupedQueryAttention(window=None | W)` on the Pallas flash kernel
(`ops/pallas_kernels.flash_attention(causal=True, window=W)`; dense
attention with the same mask off the TPU and at short T), `DecoderLayer`
over `parallel.layers.RoutedExperts`, and `DecoderLM`, which takes the
published ``layer_types`` pattern.  Trained like every other model here:
`Trainer` + `gluon.FusedTrainStep`, one donated program a step.  The
plain f32 reference it is held to is `models/reference/mellum2.py`.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .. import initializer as init
from .. import numpy_extension as npx
from ..context import on_tpu
from ..gluon import nn
from ..gluon.block import HybridBlock
from ..gluon.parameter import Parameter
from ..ops.invoke import invoke
from ..parallel.layers import RoutedExperts
from ..parallel.mesh import PartitionSpec
from .transformer import FLASH_AUTO_MIN_T_TRAINING, _flash_shape_ok

__all__ = ["RMSNorm", "rope_inv_freq", "GroupedQueryAttention",
           "DecoderLayer", "DecoderLM", "CausalLMLoss"]


class RMSNorm(HybridBlock):
    """x / sqrt(mean(x^2) + eps) * gamma, the statistics in f32."""

    def __init__(self, units, epsilon=1e-6, dtype="float32"):
        super().__init__()
        self._epsilon = epsilon
        self.gamma = Parameter("gamma", shape=(units,), dtype=dtype,
                               init=init.One())

    def forward(self, x):
        eps = self._epsilon

        def f(x, gamma):
            xf = x.astype(jnp.float32)
            scale = jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True)
                                  + eps)
            return (xf * scale * gamma.astype(jnp.float32)).astype(x.dtype)

        return invoke(f, (x, self.gamma.data()), name="rms_norm")

    @staticmethod
    def partition_rules(axis_name="tp", prefix=".*"):
        return [(prefix + r"gamma$", PartitionSpec())]


def rope_inv_freq(head_dim, rope):
    """(inverse frequencies as a list of head_dim/2 floats, the factor on
    cos and sin) of one ``rope_parameters`` entry: ``rope_type`` "default"
    gives theta^(-2i/d); "yarn" (Peng et al. 2023) divides the low
    frequencies by ``factor``, keeps the high ones, ramps linearly between
    the dimensions that turn ``beta_slow`` and ``beta_fast`` times over
    ``original_max_position_embeddings``, and scales cos and sin by
    ``attention_factor`` (0.1 ln factor + 1 where the entry gives none).
    Host arithmetic in Python floats: the tables are constants of a trace.
    """
    theta, half = float(rope["rope_theta"]), head_dim // 2
    freq = [theta ** (-2.0 * i / head_dim) for i in range(half)]
    if rope.get("rope_type", "default") != "yarn":
        return freq, 1.0
    factor = float(rope["factor"])
    orig = rope["original_max_position_embeddings"]

    def turns_dim(turns):
        return head_dim * math.log(orig / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(turns_dim(rope["beta_fast"])), 0)
    high = min(math.ceil(turns_dim(rope["beta_slow"])), head_dim - 1)
    span = max(high - low, 0.001)
    ramp = [min(max((i - low) / span, 0.0), 1.0) for i in range(half)]
    scale = rope.get("attention_factor")
    if scale is None:
        scale = 0.1 * math.log(factor) + 1.0
    return [f / factor * r + f * (1.0 - r) for f, r in zip(freq, ramp)], \
        float(scale)


def _rotate(x, inv_freq, scale):
    """Rotate-half rotary positions on x (B, T, heads, D), in f32."""
    t, d = x.shape[1], x.shape[-1]
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * \
        jnp.asarray(inv_freq, jnp.float32)[None, :]
    angle = jnp.concatenate([angle, angle], axis=-1)[None, :, None, :]
    xf = x.astype(jnp.float32)
    turned = jnp.concatenate([-xf[..., d // 2:], xf[..., :d // 2]], axis=-1)
    return ((xf * jnp.cos(angle) + turned * jnp.sin(angle)) * scale).astype(
        x.dtype)


class GroupedQueryAttention(HybridBlock):
    """Causal self-attention with ``num_kv_heads`` key-value heads shared
    by groups of ``num_heads / num_kv_heads`` query heads (query head i
    reads key-value head i // group), rotary positions from one
    ``rope_parameters`` entry, and an optional sliding ``window``: query t
    sees key j iff 0 <= t - j < window.  No biases.

    ``use_flash="auto"`` takes the Pallas kernel on a TPU from
    `FLASH_AUTO_MIN_T_TRAINING` keys on (the kernel reads K and V through
    its index maps, un-repeated, and skips blocks outside the band);
    elsewhere dense attention applies the same mask.  True forces the
    kernel (interpreted off the TPU), False dense.
    """

    def __init__(self, units, num_heads, num_kv_heads, head_dim, rope,
                 window=None, dtype="float32", use_flash="auto"):
        super().__init__()
        if num_heads % num_kv_heads:
            raise ValueError("num_kv_heads must divide num_heads")
        self._heads = (num_heads, num_kv_heads, head_dim)
        self._window = window
        self._use_flash = use_flash
        self._rope = rope_inv_freq(head_dim, rope)
        std = init.Normal(0.02)

        def dense(out, in_units=units):
            return nn.Dense(out, flatten=False, use_bias=False,
                            weight_initializer=std, dtype=dtype,
                            in_units=in_units)

        self.query = dense(num_heads * head_dim)
        self.key = dense(num_kv_heads * head_dim)
        self.value = dense(num_kv_heads * head_dim)
        self.proj = dense(units, num_heads * head_dim)

    @staticmethod
    def partition_rules(axis_name="tp", prefix=".*"):
        """Megatron: Q/K/V split by heads (weight dim 0), the output
        projection by its input."""
        return [
            (prefix + r"(query|key|value)\.weight$",
             PartitionSpec(axis_name, None)),
            (prefix + r"proj\.weight$", PartitionSpec(None, axis_name)),
        ]

    def _flash_now(self, t):
        if self._use_flash == "auto":
            return on_tpu() and t >= FLASH_AUTO_MIN_T_TRAINING and \
                _flash_shape_ok(t)
        return bool(self._use_flash)

    def forward(self, x):
        b, t, _ = x.shape
        h, hkv, d = self._heads
        window = self._window
        inv_freq, scale = self._rope

        def rotary(q, k):
            return _rotate(q, inv_freq, scale), _rotate(k, inv_freq, scale)

        q, k = invoke(rotary, (self.query(x).reshape(b, t, h, d),
                               self.key(x).reshape(b, t, hkv, d)),
                      name="rotary")
        v = self.value(x).reshape(b, t, hkv, d)
        if self._flash_now(t):
            out = npx.flash_attention(
                q.swapaxes(1, 2), k.swapaxes(1, 2), v.swapaxes(1, 2),
                causal=True, window=window).swapaxes(1, 2)
        else:
            def dense(q, k, v):
                qg = q.reshape(b, t, hkv, h // hkv, d)
                s = jnp.einsum("btkgd,bskd->bkgts", qg, k,
                               preferred_element_type=jnp.float32) \
                    / math.sqrt(d)
                dist = jnp.arange(t)[:, None] - jnp.arange(t)[None, :]
                seen = dist >= 0
                if window is not None:
                    seen = seen & (dist < window)
                p = jax.nn.softmax(jnp.where(seen, s, -1e30), axis=-1)
                return jnp.einsum("bkgts,bskd->btkgd", p.astype(v.dtype), v)

            out = invoke(dense, (q, k, v), name="gqa_dense_attention")
        return self.proj(out.reshape(b, t, h * d))


class _PreNormAttention(HybridBlock):
    """Attention(RMSNorm(h)): the half of a layer that `remat` recomputes."""

    def __init__(self, units, num_heads, num_kv_heads, head_dim, rope,
                 window, epsilon, dtype, use_flash):
        super().__init__()
        self.norm = RMSNorm(units, epsilon, dtype)
        self.attention = GroupedQueryAttention(
            units, num_heads, num_kv_heads, head_dim, rope, window=window,
            dtype=dtype, use_flash=use_flash)

    def forward(self, x):
        return self.attention(self.norm(x))


class DecoderLayer(HybridBlock):
    """h + Attention(RMSNorm(h)), then h + Experts(RMSNorm(h)).

    ``remat`` recomputes the attention half in the backward pass from the
    layer's input (q, k, v, the kernel's output and log-sum-exp are not
    kept); the experts keep only their input and picks by themselves
    (`parallel.moe.routed_experts`)."""

    def __init__(self, units, num_heads, num_kv_heads, head_dim, rope,
                 window, expert_hidden, num_experts, top_k,
                 experts_held=None, ep_rank=0, epsilon=1e-6,
                 dtype="float32", use_flash="auto", remat=False):
        super().__init__()
        self._remat = remat
        self.attend = _PreNormAttention(units, num_heads, num_kv_heads,
                                        head_dim, rope, window, epsilon,
                                        dtype, use_flash)
        self.ffn_norm = RMSNorm(units, epsilon, dtype)
        self.experts = RoutedExperts(units, expert_hidden, num_experts,
                                     top_k, experts_held=experts_held,
                                     ep_rank=ep_rank, dtype=dtype)

    def forward(self, x):
        attend = npx.remat(self.attend) if self._remat else self.attend
        x = x + attend(x)
        return x + self.experts(self.ffn_norm(x))


class DecoderLM(HybridBlock):
    """Token embedding -> ``layer_types`` decoder layers -> RMSNorm ->
    an untied output head: ids (B, T) -> logits (B, T, vocab).

    ``layer_types[l]`` is "sliding_attention" (``window`` keys, rotary
    ``rope_parameters["sliding_attention"]``) or "full_attention" (causal,
    ``rope_parameters["full_attention"]``).  ``experts_held``/``ep_rank``
    and ``vocab_size`` may be one chip's share of a deployment (see
    `RoutedExperts`; a sliced vocabulary is simply a smaller one).
    ``remat`` recomputes each layer's attention half in the backward pass.
    """

    def __init__(self, vocab_size, units, layer_types, num_heads,
                 num_kv_heads, head_dim, rope_parameters, window,
                 expert_hidden, num_experts, top_k, experts_held=None,
                 ep_rank=0, epsilon=1e-6, dtype="float32",
                 use_flash="auto", remat=False):
        super().__init__()
        self._layer_names = []
        self.embed = nn.Embedding(vocab_size, units, dtype=dtype,
                                  weight_initializer=init.Normal(0.02))
        for i, kind in enumerate(layer_types):
            if kind not in ("sliding_attention", "full_attention"):
                raise ValueError(f"layer_types[{i}] = {kind!r}")
            setattr(self, f"layer{i}", DecoderLayer(
                units, num_heads, num_kv_heads, head_dim,
                rope_parameters[kind],
                window if kind == "sliding_attention" else None,
                expert_hidden, num_experts, top_k,
                experts_held=experts_held, ep_rank=ep_rank, epsilon=epsilon,
                dtype=dtype, use_flash=use_flash, remat=remat))
            self._layer_names.append(f"layer{i}")
        self.norm = RMSNorm(units, epsilon, dtype)
        self.head = nn.Dense(vocab_size, flatten=False, use_bias=False,
                             weight_initializer=init.Normal(0.02),
                             dtype=dtype, in_units=units)

    def forward(self, ids):
        x = self.embed(ids)
        for name in self._layer_names:
            x = getattr(self, name)(x)
        return self.head(self.norm(x))


class CausalLMLoss(HybridBlock):
    """Mean next-token cross-entropy of a `DecoderLM`: position t
    predicts ids[:, t+1]; the log-softmax is taken in f32."""

    def __init__(self, model):
        super().__init__()
        self.model = model

    def forward(self, ids):
        logits = self.model(ids)[:, :-1]
        logp = npx.log_softmax(logits.astype("float32"), axis=-1)
        return -npx.pick(logp, ids[:, 1:], axis=-1).mean()
