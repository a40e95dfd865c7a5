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
with a shared expert.  For Xing4.0: `LatentAttention` with a q latent and
YaRN-rotated shared channels, mHC's residual streams (`HyperConnection`,
arXiv:2512.24880) and a `MultiTokenPrediction` module (DeepSeek-V3).
Trained like every other model here: `Trainer` + `gluon.FusedTrainStep`,
one donated program a step.  The plain f32 references they are held to are
`models/reference/mellum2.py`, `models/reference/kimi_linear.py` and
`models/reference/xing4.py`.
"""
from __future__ import annotations

import functools
import math
import weakref

import jax
import jax.numpy as jnp
import numpy as onp

from .. import initializer as init
from .. import numpy_extension as npx
from ..context import on_tpu
from ..gluon import nn
from ..gluon.block import HybridBlock
from ..gluon.parameter import Parameter
from ..ops import hyper_connection as _hc
from ..ops import linear_attention as _la
from ..ops.invoke import invoke
from ..parallel.layers import RoutedExperts
from ..parallel.mesh import PartitionSpec
from .transformer import FLASH_AUTO_MIN_T_TRAINING, _flash_shape_ok

__all__ = ["RMSNorm", "rope_inv_freq", "GroupedQueryAttention",
           "KimiDeltaAttention", "LatentAttention", "SwiGLU",
           "HyperConnection", "DecoderLayer", "MultiTokenPrediction",
           "DecoderLM", "CausalLMLoss", "mtp_losses"]

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
    """Multi-head latent attention (DeepSeek-V2's MLA) in the expanded form
    training uses (no weight absorption): q = W_q x in heads of ``qk_nope +
    qk_rope``; [c | k_r] = W_kva x with c the ``kv_lora_rank`` latent;
    [k_nope | v] = W_kvb RMSNorm(c) per head; k = [k_nope | k_r], the one
    k_r shared by every head; causal softmax attention scaled by
    ``softmax_scale`` ((qk_nope + qk_rope)^-1/2 where None); y = W_o of the
    heads' ``v_head_dim`` outputs.

    ``q_lora_rank`` puts q through a latent of its own: q = W_qb
    RMSNorm(W_qa x).  ``rope`` (one ``rope_parameters`` entry) rotates the
    ``qk_rope`` channels of q and the shared k_r by position (rotate-half,
    `rope_inv_freq`'s table); without it they keep their name and width and
    are never rotated (Kimi-Linear's `mla_use_nope`).

    ``use_flash`` as `GroupedQueryAttention`'s: the Pallas kernel takes the
    two head sizes (q.k 192, v 128)."""

    def __init__(self, units, num_heads, qk_nope_head_dim, qk_rope_head_dim,
                 v_head_dim, kv_lora_rank, epsilon=1e-5, dtype="float32",
                 use_flash="auto", q_lora_rank=None, rope=None,
                 softmax_scale=None):
        super().__init__()
        self._shape = (num_heads, qk_nope_head_dim, qk_rope_head_dim,
                       v_head_dim, kv_lora_rank)
        self._use_flash = use_flash
        self._rope = None if rope is None else \
            rope_inv_freq(qk_rope_head_dim, rope)
        self._scale = softmax_scale or \
            (qk_nope_head_dim + qk_rope_head_dim) ** -0.5
        q_width = num_heads * (qk_nope_head_dim + qk_rope_head_dim)
        if q_lora_rank is None:
            self.q_proj = _dense(q_width, units, dtype)
        else:
            self.q_a = _dense(q_lora_rank, units, dtype)
            self.q_norm = RMSNorm(q_lora_rank, epsilon, dtype)
            self.q_b = _dense(q_width, q_lora_rank, dtype)
        self.kv_a = _dense(kv_lora_rank + qk_rope_head_dim, units, dtype)
        self.kv_norm = RMSNorm(kv_lora_rank, epsilon, dtype)
        self.kv_b = _dense(num_heads * (qk_nope_head_dim + v_head_dim),
                           kv_lora_rank, dtype)
        self.o_proj = _dense(units, num_heads * v_head_dim, dtype)

    @staticmethod
    def partition_rules(axis_name="tp", prefix=".*"):
        """Heads over ``axis_name`` (q and the up-projections by their
        outputs, the output projection by its input); the down-projections
        and the latents' norms, shared by every head, replicated."""
        return [
            (prefix + r"(q_proj|q_b|kv_b)\.weight$",
             PartitionSpec(axis_name, None)),
            (prefix + r"o_proj\.weight$", PartitionSpec(None, axis_name)),
            (prefix + r"(q_a|kv_a)\.weight$", PartitionSpec()),
            (prefix + r"(q_norm|kv_norm)\.gamma$", PartitionSpec()),
        ]

    def _flash_now(self, t):
        return _takes_flash(self._use_flash, t)

    def forward(self, x):
        b, t, _ = x.shape
        h, dn, dr, dv, rank = self._shape
        scale = self._scale
        q = self.q_proj(x) if hasattr(self, "q_proj") else \
            self.q_b(self.q_norm(self.q_a(x)))
        q = q.reshape(b, t, h, dn + dr)
        latent = self.kv_a(x)
        if self._rope is not None:
            inv_freq, factor = self._rope

            def rotary(q, latent):
                k_r = _rotate(latent[:, :, None, rank:], inv_freq, factor)
                return (jnp.concatenate(
                    [q[..., :dn], _rotate(q[..., dn:], inv_freq, factor)],
                    axis=-1),
                    jnp.concatenate([latent[..., :rank], k_r[:, :, 0]],
                                    axis=-1))

            q, latent = invoke(rotary, (q, latent), name="mla_rotary")
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


def sinkhorn(logits, iters, eps):
    """Sinkhorn-Knopp onto the doubly stochastic matrices, f32 (..., n, n):
    exp(logits) normalised over each row, then over each column, ``iters``
    times each.  The first row normalisation is a softmax (the row's max
    subtracted: the same ratios, finite for any logits); every later
    denominator is the sum + ``eps``."""
    m = jax.nn.softmax(logits, axis=-1)
    m = m / (jnp.sum(m, axis=-2, keepdims=True) + eps)
    for _ in range(iters - 1):
        m = m / (jnp.sum(m, axis=-1, keepdims=True) + eps)
        m = m / (jnp.sum(m, axis=-2, keepdims=True) + eps)
    return m


def _stream_sum(x, weights):
    """sum_j weights[..., j] * x[..., j, :] in f32: (B, T, n, U) streams
    to one (B, T, U) row, as n broadcast multiply-adds (an einsum would make
    a matmul of width n).  XLA does not fuse them into one pass over x: it
    writes each stream's slice as a fusion of its own and keeps f32 stream
    tensors (PERF.md section 5), which is why the TPU takes the kernels of
    `ops/hyper_connection.py` where the shapes allow."""
    return sum(weights[..., j, None] * x[:, :, j].astype(jnp.float32)
               for j in range(x.shape[2]))


def _hc_combine(x, y, post, res):
    """H_res X + H_post^T y: the streams after a sublayer whose output is y
    (B, T, U), in x's dtype; `mhc_post_fwd` and `mhc_post_bwd` where
    `ops.hyper_connection.takes_kernels`."""
    if _hc.takes_kernels(x.shape[-1]):
        return _hc.combine(x, y, post, res)
    n = x.shape[2]
    mixed = jnp.stack([_stream_sum(x, res[:, :, i]) for i in range(n)],
                      axis=2)
    return (mixed + post[..., None] * y.astype(jnp.float32)[:, :, None]) \
        .astype(x.dtype)


class HyperConnection(HybridBlock):
    """One sublayer's mixes of ``streams`` residual streams (mHC,
    arXiv:2512.24880).  For X (B, T, n, U):

        x^   = RMSNorm(vec X) with a gain of n*U
        z    = x^ phi                              (n + n + n^2 logits)
        H_pre  = sigmoid(alpha_0 z_pre + b_pre)          (n,)
        H_post = 2 sigmoid(alpha_1 z_post + b_post)      (n,)
        H_res  = `sinkhorn`(clamp(alpha_2 z_res + b_res))  (n, n)

    and returns (H_pre X (B, T, U) in X's dtype, H_post, H_res) in f32;
    the sublayer's output y goes back in by `_hc_combine`: X <- H_res X +
    H_post^T y (`around` does both about a sublayer).  The norm's per-row
    scale is applied after the projection (a scalar per row commutes with
    it) and the gain folded into phi, so that no normalised copy of X is
    written.  At the initial alpha and b the mixes are uniform: every stream
    gets the mean of the streams plus y, a plain residual on n equal copies.

    Two implementations of one mathematics, chosen from platform and shapes
    alone (`ops.hyper_connection.takes_kernels`: on TPU, U a multiple of 128
    lanes; ``mxtpu_hyperconnection_lowerings{path}`` counts each trace of the
    mixes under the one it took):

    - "xla": the norm, the projection, H_pre X and the combine as XLA ops,
      differentiated by autodiff.  Every platform but TPU, every other
      shape, and the tests' reference.  XLA writes each stream's slice as a
      fusion of its own and keeps f32 copies of the streams (PERF.md section
      5).
    - "pallas": the pre-mix (norm, projection, H_pre X) and the combine are
      the Mosaic kernel pairs `mhc_pre_fwd` / `mhc_pre_bwd` and
      `mhc_post_fwd` / `mhc_post_bwd`, each reading a tile of tokens' n
      streams once into VMEM and writing the streams' dtype back; under
      `around` the combine's backward writes no dX of its own, the pre-mix's
      backward adds H_res^T dX' in the pass that writes dX.  H_post and H_res
      (the sigmoid, the clamp, Sinkhorn) stay XLA ops on the logits in both.
    """

    def __init__(self, units, streams, iters=20, eps=1e-6,
                 clamp=(-30.0, 30.0), epsilon=1e-6, dtype="float32"):
        super().__init__()
        width = 2 * streams + streams * streams
        self._mix = (streams, iters, eps, tuple(float(c) for c in clamp),
                     epsilon)
        self.gamma = Parameter("gamma", shape=(streams * units,),
                               dtype=dtype, init=init.One())
        self.phi = Parameter("phi", shape=(streams * units, width),
                             dtype=dtype, init=init.Normal(0.02))
        self.alpha = Parameter("alpha", shape=(3,), dtype="float32",
                               init=init.Constant(0.01))
        self.bias = Parameter("bias", shape=(width,), dtype="float32",
                              init=init.Zero())

    @staticmethod
    def partition_rules(axis_name="tp", prefix=".*"):
        """Replicated: every chip mixes its own tokens' streams."""
        return [(prefix + r"(gamma|phi|alpha|bias)$", PartitionSpec())]

    def forward(self, x):
        return self._mixes(x)[:3]

    def around(self, x, sublayer):
        """X <- H_res X + H_post^T sublayer(H_pre X), streams in and out;
        ``sublayer`` maps NDArrays (B, T, U) to (B, T, U)."""
        u, post, res, streams = self._mixes(x)
        y = sublayer(u)
        if _hc.takes_kernels(x.shape[-1]):
            return invoke(functools.partial(_hc.combine, folded=True),
                          (streams, y, post, res), name="mhc_combine")
        return invoke(_hc_combine, (x, y, post, res), name="mhc_combine")

    def _mixes(self, x):
        """(H_pre X, H_post, H_res, and on the kernel path the streams for
        `ops.hyper_connection.combine`'s fold, None on the XLA path)."""
        from .. import telemetry
        n, iters, eps, (lo, hi), norm_eps = self._mix
        b, t = x.shape[:2]
        kernels = _hc.takes_kernels(x.shape[-1])
        telemetry.counter(
            "mxtpu_hyperconnection_lowerings", "mHC stream mixes traced, by "
            "the implementation taken", labelnames=("path",)
        ).labels(path="pallas" if kernels else "xla").inc()

        def post_and_res(z, alpha, bias):
            post = 2.0 * jax.nn.sigmoid(alpha[1] * z[..., n:2 * n]
                                        + bias[n:2 * n])
            res = sinkhorn(jnp.clip(alpha[2] * z[..., 2 * n:] + bias[2 * n:],
                                    lo, hi).reshape(z.shape[:-1] + (n, n)),
                           iters, eps)
            return post, res

        def mixes(x, gamma, phi, alpha, bias):
            w = (gamma.astype(jnp.float32)[:, None]
                 * phi.astype(jnp.float32)).astype(phi.dtype)
            if kernels:
                return _hc.mixes(x, w, alpha, bias, post_and_res, norm_eps)
            flat = x.reshape(b, t, -1)
            xf = flat.astype(jnp.float32)
            scale = jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True)
                                  + norm_eps)
            z = jnp.dot(flat, w, preferred_element_type=jnp.float32) * scale
            pre = jax.nn.sigmoid(alpha[0] * z[..., :n] + bias[:n])
            return (_stream_sum(x, pre).astype(x.dtype),
                    *post_and_res(z, alpha, bias))

        # host time only: under a trace this is the trace's, per step none
        with telemetry.span("mhc.trace", layer=self.phi.name, tokens=b * t):
            out = invoke(mixes, (x, self.gamma.data(), self.phi.data(),
                                 self.alpha.data(), self.bias.data()),
                         name="mhc_mixes")
        return tuple(out) if kernels else (*out, None)


class _MixedAttention(_PreNormAttention):
    """The attention half on ``hc``'s streams: X -> H_res X + H_post^T
    Attention(RMSNorm(H_pre X)), streams in and out."""

    def __init__(self, units, attention, epsilon, dtype, hc):
        super().__init__(units, attention, epsilon, dtype)
        self.hc = hc

    def forward(self, x):
        return self.hc.around(x, super().forward)


class DecoderLayer(HybridBlock):
    """h + Attention(RMSNorm(h)), then h + FFN(RMSNorm(h)).

    ``attention`` is the attention block of the layer's kind
    (`GroupedQueryAttention`, `KimiDeltaAttention`, `LatentAttention`).
    The FFN is ``experts`` (a `RoutedExperts`) plus ``shared`` (a `SwiGLU`
    on every token, or None), or ``mlp`` (a dense `SwiGLU`).

    ``hc_mult`` = n changes the residual path to mHC's: the layer takes and
    gives n streams (B, T, n, U), and each half is a sublayer under a
    `HyperConnection` of its own (``hc``: its ``iters``, ``eps`` and
    ``clamp``): X <- H_res X + H_post^T F(H_pre X).

    ``remat`` recomputes the attention half in the backward pass from the
    layer's input (q, k, v, a kernel's output and log-sum-exp, the scan's
    per-chunk states are not kept); the experts keep only their input and
    picks by themselves (`parallel.moe.routed_experts`).  Under mHC it
    recomputes the whole layer from its input streams (`run`): a layer then
    keeps n x U a token, where its halves' mixes, FFN and output would keep
    several times that; and a layer built as an earlier one was (``like``,
    which `DecoderLM` sets) runs through that one's trace."""

    def __init__(self, units, attention, experts=None, shared=None, mlp=None,
                 epsilon=1e-6, dtype="float32", remat=False, hc_mult=None,
                 hc=None):
        super().__init__()
        if (experts is None) == (mlp is None) or (mlp and shared):
            raise ValueError("a layer's FFN is routed experts (with or "
                             "without a shared expert) or one dense mlp")
        self._remat = remat
        self._streams = hc_mult
        # the layer whose trace this one runs through, read when it first
        # runs: () for its own; a tuple, since a Block attribute is a child
        self.like = ()
        if hc_mult is None:
            self.attend = _PreNormAttention(units, attention, epsilon, dtype)
        else:
            def mixes():
                return HyperConnection(units, hc_mult, epsilon=epsilon,
                                       dtype=dtype, **(hc or {}))
            self.attend = _MixedAttention(units, attention, epsilon, dtype,
                                          mixes())
            self.ffn_hc = mixes()
        self.ffn_norm = RMSNorm(units, epsilon, dtype)
        if mlp is not None:
            self.mlp = mlp
        else:
            self.experts = experts
            if shared is not None:
                self.shared = shared
        self._ffn = ("mlp",) if mlp is not None else \
            ("experts", "shared") if shared is not None else ("experts",)

    def run(self, x):
        """The layer on x, recomputed whole in the backward pass where it is
        on mHC's streams under ``remat``, through the trace of ``like``.  The
        streams cross that boundary flat, (B, T, n U): on the TPU a
        (B, T, n, U) tensor is laid out in tiles of n x 128 and the kernels'
        flat rows in tiles of 8 tokens, so every crossing in the 4-D form
        was a relayout of the streams each way."""
        if self._streams is None or not self._remat:
            return self(x)
        like = self.like[0] if self.like else self
        y = npx.remat(self, like=like)(x.reshape(x.shape[:2] + (-1,)))
        if like is not self and "experts" in self._ffn:
            # set as a layer is traced, and `parallel.moe.expert_loads` reads it
            self.experts.picks = like.experts.picks
        return y.reshape(x.shape)

    def forward(self, x):
        if self._streams is None:
            attend = npx.remat(self.attend) if self._remat else self.attend
            x = x + attend(x)
            m = self.ffn_norm(x)
            for name in self._ffn:
                x = x + getattr(self, name)(m)
            return x

        def ffn(u):
            m = self.ffn_norm(u)
            y = getattr(self, self._ffn[0])(m)
            for name in self._ffn[1:]:
                y = y + getattr(self, name)(m)
            return y

        shape = x.shape     # (B, T, n U) from `run`, else (B, T, n, U)
        x = x.reshape(shape[:2] + (self._streams, -1))
        return self.ffn_hc.around(self.attend(x), ffn).reshape(shape)


def _expand(x, n):
    """n copies of x (B, T, U) as the streams (B, T, n, U), written as the
    flat row (B, T, n U) that `DecoderLayer.run` and the kernels read."""
    return invoke(lambda x: jnp.concatenate([x] * n, axis=-1).reshape(
        x.shape[:2] + (n,) + x.shape[2:]), (x,), name="mhc_expand")


def _merge(x):
    """The streams (B, T, n, U) summed to one row, in x's dtype, read as the
    flat row (B, T, n U)."""
    def merge(x):
        b, t, n, units = x.shape
        flat = x.reshape(b, t, n * units).astype(jnp.float32)
        return sum(flat[..., j * units:(j + 1) * units]
                   for j in range(n)).astype(x.dtype)

    return invoke(merge, (x,), name="mhc_merge")


class MultiTokenPrediction(HybridBlock):
    """DeepSeek-V3's multi-token prediction module (arXiv:2412.19437 §2.2),
    depth 1: from the main model's final state h_t (before its norm) and
    the embedding of token t+1,

        h' = W_eh [RMSNorm(h_t) ; RMSNorm(Emb(t+1))]

    through one decoder ``layer`` (on ``streams`` mHC streams where given:
    h' expanded, the layer, the streams summed), then RMSNorm; the model's
    shared head turns that into logits for token t+2.

    ``mtp_loss`` (1,) holds the last training step's cross-entropy of this
    head as auxiliary state (`CausalLMLoss` writes it; no host sync inside
    the step); `mtp_losses()` reads it and publishes ``mxtpu_mtp_loss``."""

    def __init__(self, units, layer, streams=None, epsilon=1e-6,
                 dtype="float32"):
        super().__init__()
        self._streams = streams
        self.hnorm = RMSNorm(units, epsilon, dtype)
        self.enorm = RMSNorm(units, epsilon, dtype)
        self.eh_proj = _dense(units, 2 * units, dtype)
        self.layer = layer
        self.norm = RMSNorm(units, epsilon, dtype)
        self.mtp_loss = Parameter("mtp_loss", shape=(1,), dtype="float32",
                                  init=init.Zero(), differentiable=False)
        _MTP_MODULES.add(self)

    def forward(self, h, e):
        from .. import telemetry
        b, t = h.shape[:2]
        # host time only: under a trace this is the trace's, per step none
        with telemetry.span("mtp.trace", layer=self.eh_proj.weight.name,
                            tokens=b * t):
            x = self.eh_proj(invoke(
                lambda a, c: jnp.concatenate([a, c], axis=-1),
                (self.hnorm(h), self.enorm(e)), name="mtp_concat"))
            if self._streams is None:
                return self.norm(self.layer.run(x))
            return self.norm(_merge(self.layer.run(_expand(x, self._streams))))


# every live prediction module, for `mtp_losses`
_MTP_MODULES = weakref.WeakSet()


def mtp_losses():
    """{module's `mtp_loss` name: the cross-entropy its head had on the last
    training step} of every live `MultiTokenPrediction`, read from the
    auxiliary state the step wrote; publishes the ``mxtpu_mtp_loss`` gauge
    and one ``mtp.loss`` flight-recorder event a module."""
    from .. import observe, telemetry
    mods = sorted((m for m in _MTP_MODULES if m.mtp_loss._data is not None),
                  key=lambda m: m.mtp_loss.name)
    values = jax.device_get([m.mtp_loss.data()._data for m in mods])
    gauge = telemetry.gauge(
        "mxtpu_mtp_loss", "cross-entropy of the multi-token prediction "
        "head (token t+2), last training step", labelnames=("layer",))
    out = {}
    for mod, value in zip(mods, values):
        name, value = mod.mtp_loss.name, float(value[0])
        gauge.labels(layer=name).set(value)
        observe.record("mtp", "mtp.loss", layer=name, loss=value)
        out[name] = value
    return out


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

    ``hc_mult`` = n (with ``hc``, `HyperConnection`'s arguments) runs every
    layer on mHC's n residual streams: the embedding enters as n copies,
    and the streams are summed before the final norm.  ``mtp`` (``weight``
    λ) adds a `MultiTokenPrediction` module whose layer is of the last
    layer's kinds, sharing the embedding and the head; the model then
    returns (logits, the prediction head's logits for token t+2), the
    latter at every position (the last one's "next token" is token 0 and
    feeds no loss), and `CausalLMLoss` adds λ times their cross-entropy.
    """

    def __init__(self, vocab_size, units, layer_types, num_heads=None,
                 num_kv_heads=None, head_dim=None, rope_parameters=None,
                 window=None, expert_hidden=None, num_experts=None,
                 top_k=None, experts_held=None, ep_rank=0, epsilon=1e-6,
                 dtype="float32", use_flash="auto", remat=False,
                 mlp_layer_types=None, dense_hidden=None, shared_hidden=None,
                 router=None, kda=None, mla=None, hc_mult=None, hc=None,
                 mtp=None):
        super().__init__()
        self._layer_names = []
        self._streams = hc_mult
        self.embed = nn.Embedding(vocab_size, units, dtype=dtype,
                                  weight_initializer=init.Normal(0.02))
        ffn_kinds = mlp_layer_types or ["sparse"] * len(layer_types)

        def make_layer(kind, ffn):
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
            return DecoderLayer(units, attention, epsilon=epsilon,
                                dtype=dtype, remat=remat, hc_mult=hc_mult,
                                hc=hc, **parts)

        for i, (kind, ffn) in enumerate(zip(layer_types, ffn_kinds)):
            if kind not in ATTENTION_KINDS:
                raise ValueError(f"layer_types[{i}] = {kind!r}")
            if ffn not in FFN_KINDS:
                raise ValueError(f"mlp_layer_types[{i}] = {ffn!r}")
            setattr(self, f"layer{i}", make_layer(kind, ffn))
            self._layer_names.append(f"layer{i}")
        self.norm = RMSNorm(units, epsilon, dtype)
        self.head = nn.Dense(vocab_size, flatten=False, use_bias=False,
                             weight_initializer=init.Normal(0.02),
                             dtype=dtype, in_units=units)
        self.layer_kinds = [(k, f) for k, f in zip(layer_types, ffn_kinds)]
        self.mtp_weight = None
        layers = [getattr(self, name) for name in self._layer_names]
        if mtp is not None:
            self.mtp_weight = float(mtp["weight"])
            self.mtp = MultiTokenPrediction(
                units, make_layer(layer_types[-1], ffn_kinds[-1]), hc_mult,
                epsilon, dtype)
            layers.append(self.mtp.layer)
        first = {}      # layers of one kind share the first one's trace
        for kinds, layer in zip(self.layer_kinds + self.layer_kinds[-1:],
                                layers):
            if first.setdefault(kinds, layer) is not layer:
                layer.like = (first[kinds],)

    def hidden(self, ids):
        """The final state before the norm (B, T, U), the streams summed
        under mHC, and the embedding of ids (B, T, U)."""
        e = self.embed(ids)
        x = e if self._streams is None else _expand(e, self._streams)
        for name in self._layer_names:
            x = getattr(self, name).run(x)
        return (x if self._streams is None else _merge(x)), e

    def forward(self, ids):
        h, e = self.hidden(ids)
        logits = self.head(self.norm(h))
        if self.mtp_weight is None:
            return logits
        after = invoke(lambda e: jnp.roll(e, -1, axis=1), (e,),
                       name="mtp_next_embedding")
        return logits, self.head(self.mtp(h, after))


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
    predicts ids[:, t+1]; the log-sum-exp is taken in f32.  A model with a
    multi-token prediction module adds λ (its ``mtp_weight``) times that
    head's mean cross-entropy over positions 0..T-3, position t predicting
    ids[:, t+2], and writes that cross-entropy to the module's
    ``mtp_loss`` in training."""

    def __init__(self, model):
        super().__init__()
        self.model = model

    def forward(self, ids):
        out = self.model(ids)
        if self.model.mtp_weight is None:
            return invoke(_next_token_nll, (out[:, :-1], ids[:, 1:]),
                          name="next_token_nll")
        from ..ops.aux_scope import apply_aux_update
        from ..ops.invoke import is_training
        logits, ahead = out
        main = invoke(_next_token_nll, (logits[:, :-1], ids[:, 1:]),
                      name="next_token_nll")
        extra = invoke(_next_token_nll, (ahead[:, :-2], ids[:, 2:]),
                       name="mtp_nll")
        if is_training():
            apply_aux_update(self.model.mtp.mtp_loss.data(), invoke(
                lambda v: jax.lax.stop_gradient(v).reshape(1), (extra,),
                name="mtp_loss_state"))
        return main + self.model.mtp_weight * extra
