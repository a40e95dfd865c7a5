"""Decoder language models: pre-norm blocks whose attention half is one of
three kinds (grouped-query causal attention with an optional sliding window
and rotary positions of a kind per layer type; Kimi Delta Attention, a
linear-attention layer; latent attention, MLA, without positions) and whose
FFN half is one of two (sparse routed SwiGLU experts, with or without a
shared expert; a dense SwiGLU).

The block set `models/transformer.py` (a post-norm encoder) lacks:
`RMSNorm`, rotary tables with plain and YaRN-scaled frequencies,
`GroupedQueryAttention(window=None | W)` on the Pallas flash kernel
(`ops/pallas_kernels.flash_attention(causal=True, window=W)`; dense
attention with the same mask off the TPU and at short T), `DecoderLayer`
over `parallel.layers.RoutedExperts`, and `DecoderLM`, which takes the
published ``layer_types`` pattern.  For Kimi-Linear (arXiv:2510.26692):
`KimiDeltaAttention` on `ops/linear_attention.kda` (a chunked scan, its
first phase a Pallas kernel pair on the TPU) behind
short causal convolutions, `LatentAttention` (q.k heads of 192, v heads of
128, on the flash kernel's two head sizes), `SwiGLU`, and a sigmoid router
with a shared expert.  Trained like every other model here: `Trainer` +
`gluon.FusedTrainStep`, one donated program a step.  The plain f32
references they are held to are `models/reference/mellum2.py` and
`models/reference/kimi_linear.py`.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as onp

from .. import initializer as init
from .. import numpy_extension as npx
from ..context import on_tpu
from ..gluon import nn
from ..gluon.block import HybridBlock
from ..gluon.parameter import Parameter
from ..ops import linear_attention as _la
from ..ops.invoke import invoke
from ..parallel.layers import RoutedExperts
from ..parallel.mesh import PartitionSpec
from .transformer import FLASH_AUTO_MIN_T_TRAINING, _flash_shape_ok

__all__ = ["RMSNorm", "rope_inv_freq", "GroupedQueryAttention",
           "KimiDeltaAttention", "LatentAttention", "SwiGLU",
           "DecoderLayer", "DecoderLM", "CausalLMLoss"]

ATTENTION_KINDS = ("sliding_attention", "full_attention", "kda",
                   "latent_attention")
FFN_KINDS = ("sparse", "dense")


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


def _dense(out_units, in_units, dtype, use_bias=False):
    return nn.Dense(out_units, flatten=False, use_bias=use_bias,
                    weight_initializer=init.Normal(0.02), dtype=dtype,
                    in_units=in_units)


def _takes_flash(use_flash, t):
    """``use_flash="auto"``: the Pallas kernel on a TPU from
    `FLASH_AUTO_MIN_T_TRAINING` keys on; True forces it (interpreted off
    the TPU), False dense attention."""
    if use_flash == "auto":
        return on_tpu() and t >= FLASH_AUTO_MIN_T_TRAINING and \
            _flash_shape_ok(t)
    return bool(use_flash)


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
        self.query = _dense(num_heads * head_dim, units, dtype)
        self.key = _dense(num_kv_heads * head_dim, units, dtype)
        self.value = _dense(num_kv_heads * head_dim, units, dtype)
        self.proj = _dense(units, num_heads * head_dim, dtype)

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
        return _takes_flash(self._use_flash, t)

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


class _LogUniform(init.Initializer):
    """log(U(low, high)) (``inverse_softplus=False``: `A_log`) or
    softplus^-1 of exp(U(log low, log high)) (`dt_bias`): the convention of
    `fla/layers/kda.py`.  Overrides `init_weight` itself, so that a name
    ending in "bias" is not zeroed."""

    def __init__(self, low, high, inverse_softplus):
        super().__init__(low=low, high=high, inverse_softplus=inverse_softplus)
        self._range, self._inverse = (low, high), inverse_softplus

    def init_weight(self, desc, arr):
        from .. import random as _rng
        low, high = self._range
        key = _rng.new_key()
        if not self._inverse:
            value = jnp.log(jax.random.uniform(key, arr.shape, jnp.float32,
                                               low, high))
        else:
            dt = jnp.exp(jax.random.uniform(key, arr.shape, jnp.float32,
                                            math.log(low), math.log(high)))
            value = dt + jnp.log(-jnp.expm1(-dt))
        arr._rebind(value.astype(arr.dtype))


class KimiDeltaAttention(HybridBlock):
    """Kimi Delta Attention (Kimi Linear, arXiv:2510.26692; the public
    implementation is `fla/layers/kda.py`): per head a state S (D, D) under
    the gated delta rule with a decay per key channel.

        q, k, v = SiLU(Conv(W x)), a depthwise causal convolution of
                  ``conv_size`` taps each; q, k L2-normalised per head, q
                  scaled by D^-1/2
        g    = -exp(A_log[head]) * softplus(W_f2 (W_f1 x) + dt_bias)   f32
        beta = sigmoid(W_b x)                                  one per head
        o    = `ops.linear_attention.kda(q, k, v, g, beta)`
        y    = W_o (RMSNorm_head(o) * sigmoid(W_g2 (W_g1 x) + b_g))

    Both gates are low-rank through the head size (`fla/layers/kda.py`).
    No positions: the recurrence carries order."""

    def __init__(self, units, num_heads, head_dim, conv_size=4,
                 epsilon=1e-5, dtype="float32", chunk=64):
        super().__init__()
        inner, rank = num_heads * head_dim, head_dim
        self._shape = (num_heads, head_dim, chunk, epsilon)
        self.q_proj = _dense(inner, units, dtype)
        self.k_proj = _dense(inner, units, dtype)
        self.v_proj = _dense(inner, units, dtype)
        for name in ("q_conv", "k_conv", "v_conv"):
            setattr(self, name, Parameter(name, shape=(inner, conv_size),
                                          dtype=dtype, init=init.Normal(0.02)))
        self.f_a = _dense(rank, units, dtype)
        self.f_b = _dense(inner, rank, dtype)
        self.A_log = Parameter("A_log", shape=(num_heads,), dtype="float32",
                               init=_LogUniform(1.0, 16.0, False))
        self.dt_bias = Parameter("dt_bias", shape=(inner,), dtype="float32",
                                 init=_LogUniform(1e-3, 1e-1, True))
        self.b_proj = _dense(num_heads, units, dtype)
        self.g_a = _dense(rank, units, dtype)
        self.g_b = _dense(inner, rank, dtype, use_bias=True)
        self.o_norm = Parameter("o_norm", shape=(head_dim,), dtype=dtype,
                                init=init.One())
        self.o_proj = _dense(units, inner, dtype)

    @staticmethod
    def partition_rules(axis_name="tp", prefix=".*"):
        """Heads over ``axis_name``: every projection to the heads' channels
        by its output, the convolutions and per-channel vectors with them,
        the output projection by its input; the low-rank halves and the
        head norm's gain replicated."""
        return [
            (prefix + r"(q_proj|k_proj|v_proj|f_b|b_proj|g_b)\.weight$",
             PartitionSpec(axis_name, None)),
            (prefix + r"(q_conv|k_conv|v_conv)$",
             PartitionSpec(axis_name, None)),
            (prefix + r"(g_b\.bias|dt_bias|A_log)$", PartitionSpec(axis_name)),
            (prefix + r"o_proj\.weight$", PartitionSpec(None, axis_name)),
            (prefix + r"(f_a|g_a)\.weight$", PartitionSpec()),
            (prefix + r"o_norm$", PartitionSpec()),
        ]

    def forward(self, x):
        from .. import telemetry
        b, t, _ = x.shape
        h, d, chunk, eps = self._shape

        def core(q, k, v, f, bt, gate, qw, kw, vw, a_log, dt_bias, gain):
            def head(z, w, normed):
                z = jax.nn.silu(_la.causal_conv(z, w).astype(jnp.float32))
                z = z.reshape(b, t, h, d)
                if normed:
                    z = z * jax.lax.rsqrt(
                        jnp.sum(z * z, axis=-1, keepdims=True) + 1e-6)
                return z

            qh = (head(q, qw, True) * d ** -0.5).astype(v.dtype)
            kh = head(k, kw, True).astype(v.dtype)
            vh = head(v, vw, False).astype(v.dtype)
            g = -jnp.exp(a_log)[None, None, :, None] * jax.nn.softplus(
                f.astype(jnp.float32) + dt_bias).reshape(b, t, h, d)
            beta = jax.nn.sigmoid(bt.astype(jnp.float32))
            o = _la.kda(qh, kh, vh, g, beta, chunk=chunk).astype(jnp.float32)
            o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                                  + eps) * gain.astype(jnp.float32)
            o = o * jax.nn.sigmoid(gate.astype(jnp.float32)).reshape(
                b, t, h, d)
            return o.reshape(b, t, h * d).astype(v.dtype)

        args = (self.q_proj(x), self.k_proj(x), self.v_proj(x),
                self.f_b(self.f_a(x)), self.b_proj(x), self.g_b(self.g_a(x)),
                self.q_conv.data(), self.k_conv.data(), self.v_conv.data(),
                self.A_log.data(), self.dt_bias.data(), self.o_norm.data())
        # host time only: under a trace this is the trace's, per step none
        with telemetry.span("kda.trace", layer=self.A_log.name, tokens=b * t):
            out = invoke(core, args, name="kimi_delta_attention")
        return self.o_proj(out)


class LatentAttention(HybridBlock):
    """Multi-head latent attention (DeepSeek-V2's MLA) WITHOUT positions
    (`mla_use_nope`), in the expanded form training uses (no weight
    absorption): q = W_q x in heads of ``qk_nope + qk_rope``; [c | k_r] =
    W_kva x with c the ``kv_lora_rank`` latent; [k_nope | v] = W_kvb
    RMSNorm(c) per head; k = [k_nope | k_r], the one k_r shared by every
    head; causal softmax attention scaled by (qk_nope + qk_rope)^-1/2; y =
    W_o of the heads' ``v_head_dim`` outputs.  The "rope" part of q and k
    keeps its name and width and is never rotated.

    ``use_flash`` as `GroupedQueryAttention`'s: the Pallas kernel takes the
    two head sizes (q.k 192, v 128 in Kimi-Linear)."""

    def __init__(self, units, num_heads, qk_nope_head_dim, qk_rope_head_dim,
                 v_head_dim, kv_lora_rank, epsilon=1e-5, dtype="float32",
                 use_flash="auto"):
        super().__init__()
        self._shape = (num_heads, qk_nope_head_dim, qk_rope_head_dim,
                       v_head_dim, kv_lora_rank)
        self._use_flash = use_flash
        self.q_proj = _dense(
            num_heads * (qk_nope_head_dim + qk_rope_head_dim), units, dtype)
        self.kv_a = _dense(kv_lora_rank + qk_rope_head_dim, units, dtype)
        self.kv_norm = RMSNorm(kv_lora_rank, epsilon, dtype)
        self.kv_b = _dense(num_heads * (qk_nope_head_dim + v_head_dim),
                           kv_lora_rank, dtype)
        self.o_proj = _dense(units, num_heads * v_head_dim, dtype)

    @staticmethod
    def partition_rules(axis_name="tp", prefix=".*"):
        """Heads over ``axis_name`` (q and the up-projection by their
        outputs, the output projection by its input); the down-projection
        and the latent's norm, shared by every head, replicated."""
        return [
            (prefix + r"(q_proj|kv_b)\.weight$",
             PartitionSpec(axis_name, None)),
            (prefix + r"o_proj\.weight$", PartitionSpec(None, axis_name)),
            (prefix + r"kv_a\.weight$", PartitionSpec()),
            (prefix + r"kv_norm\.gamma$", PartitionSpec()),
        ]

    def _flash_now(self, t):
        return _takes_flash(self._use_flash, t)

    def forward(self, x):
        b, t, _ = x.shape
        h, dn, dr, dv, rank = self._shape
        scale = (dn + dr) ** -0.5
        q = self.q_proj(x).reshape(b, t, h, dn + dr)
        latent = self.kv_a(x)
        kv = self.kv_b(self.kv_norm(latent[..., :rank])).reshape(
            b, t, h, dn + dv)

        def keys(kv, latent):
            shared = jnp.broadcast_to(latent[:, :, None, rank:],
                                      (b, t, h, dr))
            return jnp.concatenate([kv[..., :dn], shared], axis=-1)

        k = invoke(keys, (kv, latent), name="mla_keys")
        v = kv[..., dn:]
        if self._flash_now(t):
            out = npx.flash_attention(
                q.swapaxes(1, 2), k.swapaxes(1, 2), v.swapaxes(1, 2),
                causal=True, scale=scale).swapaxes(1, 2)
        else:
            def dense(q, k, v):
                s = jnp.einsum("bthd,bshd->bhts", q, k,
                               preferred_element_type=jnp.float32) * scale
                seen = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
                p = jax.nn.softmax(jnp.where(seen, s, -1e30), axis=-1)
                return jnp.einsum("bhts,bshd->bthd", p.astype(v.dtype), v)

            out = invoke(dense, (q, k, v), name="mla_dense_attention")
        return self.o_proj(out.reshape(b, t, h * dv))


class SwiGLU(HybridBlock):
    """down(SiLU(gate x) * up x), no biases: a dense FFN, or the shared
    expert every token passes beside the routed ones."""

    def __init__(self, units, hidden, dtype="float32"):
        super().__init__()
        self.gate = _dense(hidden, units, dtype)
        self.up = _dense(hidden, units, dtype)
        self.down = _dense(units, hidden, dtype)

    @staticmethod
    def partition_rules(axis_name="tp", prefix=".*"):
        """Megatron: gate and up split by their outputs, down by its input."""
        return [
            (prefix + r"(gate|up)\.weight$", PartitionSpec(axis_name, None)),
            (prefix + r"down\.weight$", PartitionSpec(None, axis_name)),
        ]

    def forward(self, x):
        def gated(g, u):
            return (jax.nn.silu(g.astype(jnp.float32))
                    * u.astype(jnp.float32)).astype(g.dtype)

        return self.down(invoke(gated, (self.gate(x), self.up(x)),
                                name="swiglu"))


class _PreNormAttention(HybridBlock):
    """Attention(RMSNorm(h)), whatever the attention's kind: the half of a
    layer that `remat` recomputes."""

    def __init__(self, units, attention, epsilon, dtype):
        super().__init__()
        self.norm = RMSNorm(units, epsilon, dtype)
        self.attention = attention

    def forward(self, x):
        return self.attention(self.norm(x))


class DecoderLayer(HybridBlock):
    """h + Attention(RMSNorm(h)), then h + FFN(RMSNorm(h)).

    ``attention`` is the attention block of the layer's kind
    (`GroupedQueryAttention`, `KimiDeltaAttention`, `LatentAttention`).
    The FFN is ``experts`` (a `RoutedExperts`) plus ``shared`` (a `SwiGLU`
    on every token, or None), or ``mlp`` (a dense `SwiGLU`).

    ``remat`` recomputes the attention half in the backward pass from the
    layer's input (q, k, v, a kernel's output and log-sum-exp, the scan's
    per-chunk states are not kept); the experts keep only their input and
    picks by themselves (`parallel.moe.routed_experts`)."""

    def __init__(self, units, attention, experts=None, shared=None, mlp=None,
                 epsilon=1e-6, dtype="float32", remat=False):
        super().__init__()
        if (experts is None) == (mlp is None) or (mlp and shared):
            raise ValueError("a layer's FFN is routed experts (with or "
                             "without a shared expert) or one dense mlp")
        self._remat = remat
        self.attend = _PreNormAttention(units, attention, epsilon, dtype)
        self.ffn_norm = RMSNorm(units, epsilon, dtype)
        if mlp is not None:
            self.mlp = mlp
        else:
            self.experts = experts
            if shared is not None:
                self.shared = shared
        self._ffn = ("mlp",) if mlp is not None else \
            ("experts", "shared") if shared is not None else ("experts",)

    def forward(self, x):
        attend = npx.remat(self.attend) if self._remat else self.attend
        x = x + attend(x)
        m = self.ffn_norm(x)
        for name in self._ffn:
            x = x + getattr(self, name)(m)
        return x


class DecoderLM(HybridBlock):
    """Token embedding -> ``layer_types`` decoder layers -> RMSNorm ->
    an untied output head: ids (B, T) -> logits (B, T, vocab).

    ``layer_types[l]`` names the attention of layer l: "sliding_attention"
    (``window`` keys, rotary ``rope_parameters["sliding_attention"]``),
    "full_attention" (causal, ``rope_parameters["full_attention"]``), both
    grouped-query over ``num_heads``/``num_kv_heads``/``head_dim``; "kda"
    (`KimiDeltaAttention(**kda)`); "latent_attention"
    (`LatentAttention(**mla)`).  ``mlp_layer_types[l]`` names its FFN:
    "sparse" (the default everywhere: `RoutedExperts`, with
    ``shared_hidden`` a shared `SwiGLU` expert beside them and ``router``
    the scoring arguments of `RoutedExperts`) or "dense"
    (`SwiGLU(dense_hidden)`).  ``experts_held``/``ep_rank`` and
    ``vocab_size`` may be one chip's share of a deployment (see
    `RoutedExperts`; a sliced vocabulary is simply a smaller one).
    ``remat`` recomputes each layer's attention half in the backward pass.
    """

    def __init__(self, vocab_size, units, layer_types, num_heads=None,
                 num_kv_heads=None, head_dim=None, rope_parameters=None,
                 window=None, expert_hidden=None, num_experts=None,
                 top_k=None, experts_held=None, ep_rank=0, epsilon=1e-6,
                 dtype="float32", use_flash="auto", remat=False,
                 mlp_layer_types=None, dense_hidden=None, shared_hidden=None,
                 router=None, kda=None, mla=None):
        super().__init__()
        self._layer_names = []
        self.embed = nn.Embedding(vocab_size, units, dtype=dtype,
                                  weight_initializer=init.Normal(0.02))
        ffn_kinds = mlp_layer_types or ["sparse"] * len(layer_types)
        for i, (kind, ffn) in enumerate(zip(layer_types, ffn_kinds)):
            if kind not in ATTENTION_KINDS:
                raise ValueError(f"layer_types[{i}] = {kind!r}")
            if ffn not in FFN_KINDS:
                raise ValueError(f"mlp_layer_types[{i}] = {ffn!r}")
            if kind == "kda":
                attention = KimiDeltaAttention(units, epsilon=epsilon,
                                               dtype=dtype, **kda)
            elif kind == "latent_attention":
                attention = LatentAttention(units, epsilon=epsilon,
                                            dtype=dtype, use_flash=use_flash,
                                            **mla)
            else:
                attention = GroupedQueryAttention(
                    units, num_heads, num_kv_heads, head_dim,
                    rope_parameters[kind],
                    window=window if kind == "sliding_attention" else None,
                    dtype=dtype, use_flash=use_flash)
            parts = {"mlp": SwiGLU(units, dense_hidden, dtype)} \
                if ffn == "dense" else {
                    "experts": RoutedExperts(
                        units, expert_hidden, num_experts, top_k,
                        experts_held=experts_held, ep_rank=ep_rank,
                        dtype=dtype, **(router or {})),
                    "shared": SwiGLU(units, shared_hidden, dtype)
                    if shared_hidden else None}
            setattr(self, f"layer{i}", DecoderLayer(
                units, attention, epsilon=epsilon, dtype=dtype, remat=remat,
                **parts))
            self._layer_names.append(f"layer{i}")
        self.norm = RMSNorm(units, epsilon, dtype)
        self.head = nn.Dense(vocab_size, flatten=False, use_bias=False,
                             weight_initializer=init.Normal(0.02),
                             dtype=dtype, in_units=units)
        self.layer_kinds = [(k, f) for k, f in zip(layer_types, ffn_kinds)]

    def forward(self, ids):
        x = self.embed(ids)
        for name in self._layer_names:
            x = getattr(self, name)(x)
        return self.head(self.norm(x))


@jax.custom_vjp
def _next_token_nll(logits, targets):
    """Mean over positions of logsumexp(logits) - logits[target], in f32:
    the cross-entropy with the target picked BEFORE the log-sum-exp, so that
    no (B, T, vocabulary) f32 log-softmax exists.  Written as one value and
    its gradient: left to autodiff, the loss VALUE's pick is the last reader
    of that array (1.34 GB in the Kimi cell), and the scheduler may put it
    after the whole backward pass."""
    return _next_token_nll_fwd(logits, targets)[0]


def _next_token_nll_fwd(logits, targets):
    x = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(x, axis=-1)
    picked = jnp.take_along_axis(x, targets[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - picked), (logits, lse, targets)


def _next_token_nll_bwd(res, ct):
    logits, lse, targets = res
    # softmax - onehot, from the logits as the model wrote them
    p = jnp.exp(logits.astype(jnp.float32) - lse[..., None])
    hit = jax.lax.broadcasted_iota(jnp.int32, logits.shape, logits.ndim - 1) \
        == targets[..., None]
    d = (p - hit.astype(jnp.float32)) * (ct / lse.size)
    return d.astype(logits.dtype), onp.zeros(targets.shape, jax.dtypes.float0)


_next_token_nll.defvjp(_next_token_nll_fwd, _next_token_nll_bwd)


class CausalLMLoss(HybridBlock):
    """Mean next-token cross-entropy of a `DecoderLM`: position t
    predicts ids[:, t+1]; the log-sum-exp is taken in f32."""

    def __init__(self, model):
        super().__init__()
        self.model = model

    def forward(self, ids):
        return invoke(_next_token_nll, (self.model(ids)[:, :-1], ids[:, 1:]),
                      name="next_token_nll")
