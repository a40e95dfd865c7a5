"""Pure-XLA lowerings of the reference's NN operator library.

Reference: `src/operator/nn/` (convolution.cc, pooling.cc, batch_norm.cc,
softmax.cc, fully_connected.cc, dropout.cc ... 31k LoC of CPU/cuDNN/MKLDNN
kernels).  TPU-native design: each op is a composition of `lax` primitives
that XLA tiles onto the MXU/VPU — there is no per-backend kernel zoo to
maintain, and pointwise pre/post-ops fuse into the conv/matmul automatically.

All functions here take and return raw jax arrays (dispatch and autograd are
handled by `ops/invoke.py`).  Layouts follow the reference's defaults
(NCHW/NCW/NCDHW) but NHWC is supported and preferred on TPU.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
import numpy as onp


# Dropout mask bits come from XLA's hardware RNG: see `_dropout_key`.
_DROPOUT_RNG_IMPL = "rbg"


def _tuplize(v, n):
    if v is None:
        return (1,) * n
    if isinstance(v, int):
        return (v,) * n
    t = tuple(v)
    return t * n if len(t) == 1 else t


# ---------------------------------------------------------------------------
# convolution (reference: src/operator/nn/convolution.cc)
# ---------------------------------------------------------------------------
def _conv_dimension_numbers(layout):
    # lax dimension_numbers: (lhs, rhs, out) as strings
    spatial = layout.replace("N", "").replace("C", "")
    lhs = layout
    rhs = "OI" + spatial
    return (lhs, rhs, lhs)


def convolution(data, weight, bias=None, kernel=None, stride=None, dilate=None,
                pad=None, num_filter=None, num_group=1, layout="NCHW"):
    """N-d convolution; weight layout is (num_filter, C//group, *kernel) as in
    the reference (`convolution-inl.h`)."""
    nsp = len(layout) - 2
    stride = _tuplize(stride, nsp)
    dilate = _tuplize(dilate, nsp)
    pad = _tuplize(pad if pad is not None else 0, nsp)
    pad = tuple((p, p) for p in pad)
    dn = lax.conv_dimension_numbers(data.shape, weight.shape,
                                    _conv_dimension_numbers(layout))
    # no preferred_element_type here: the conv transpose (weight gradient)
    # rejects the resulting mixed f32-cotangent/bf16-operand conv, and the
    # MXU accumulates bf16 convolutions in f32 natively anyway
    out = lax.conv_general_dilated(
        data, weight, window_strides=stride, padding=pad,
        rhs_dilation=dilate, dimension_numbers=dn,
        feature_group_count=num_group,
    ).astype(data.dtype)
    if bias is not None:
        c_axis = layout.index("C")
        shape = [1] * out.ndim
        shape[c_axis] = out.shape[c_axis]
        out = out + bias.reshape(shape)
    return out


def deconvolution(data, weight, bias=None, kernel=None, stride=None,
                  dilate=None, pad=None, adj=None, num_filter=None,
                  num_group=1, layout="NCHW"):
    """Transposed convolution (reference `deconvolution.cc`)."""
    nsp = len(layout) - 2
    stride = _tuplize(stride, nsp)
    dilate = _tuplize(dilate, nsp)
    pad_ = _tuplize(pad if pad is not None else 0, nsp)
    adj = _tuplize(adj if adj is not None else 0, nsp)
    kernel = weight.shape[2:]
    # conv_transpose padding: reference semantics out = (in-1)*s - 2p + k + adj
    pads = tuple(
        (k - 1 - p, k - 1 - p + a)
        for k, p, a in zip(
            [(kk - 1) * d + 1 for kk, d in zip(kernel, dilate)], pad_, adj)
    )
    dn = lax.conv_dimension_numbers(
        data.shape,
        (weight.shape[1] * num_group, weight.shape[0] // num_group) + tuple(kernel),
        _conv_dimension_numbers(layout))
    # weight stored (C_in, C_out//g, *k) in reference deconv; flip spatial and
    # swap in/out channels to express as a dilated conv.
    w = jnp.flip(weight, axis=tuple(range(2, weight.ndim)))
    if num_group == 1:
        w = jnp.swapaxes(w, 0, 1)
    else:
        cin, cog = weight.shape[0], weight.shape[1]
        w = w.reshape((num_group, cin // num_group, cog) + tuple(kernel))
        w = jnp.swapaxes(w, 1, 2)
        w = w.reshape((cog * num_group, cin // num_group) + tuple(kernel))
    out = lax.conv_general_dilated(
        data, w, window_strides=(1,) * nsp, padding=pads,
        lhs_dilation=stride, rhs_dilation=dilate, dimension_numbers=dn,
        feature_group_count=num_group,
    ).astype(data.dtype)
    if bias is not None:
        c_axis = layout.index("C")
        shape = [1] * out.ndim
        shape[c_axis] = out.shape[c_axis]
        out = out + bias.reshape(shape)
    return out


def _acc_type(dtype):
    # accumulate matmul/conv in f32 when inputs are bf16/f16 (MXU-native)
    if dtype in (jnp.bfloat16, jnp.float16):
        return jnp.float32
    return None


# ---------------------------------------------------------------------------
# pooling (reference: src/operator/nn/pooling.cc)
# ---------------------------------------------------------------------------
def pooling(data, kernel=None, pool_type="max", stride=None, pad=None,
            global_pool=False, count_include_pad=True, layout="NCHW",
            pooling_convention="valid"):
    nsp = len(layout) - 2
    sp_axes = tuple(i for i, c in enumerate(layout) if c not in "NC")
    if global_pool:
        if pool_type == "max":
            return jnp.max(data, axis=sp_axes, keepdims=True)
        return jnp.mean(data, axis=sp_axes, keepdims=True)
    kernel = _tuplize(kernel, nsp)
    stride = _tuplize(stride if stride is not None else kernel, nsp)
    pad = _tuplize(pad if pad is not None else 0, nsp)

    window = [1] * data.ndim
    strides = [1] * data.ndim
    pads = [(0, 0)] * data.ndim
    for ax, k, s, p in zip(sp_axes, kernel, stride, pad):
        window[ax] = k
        strides[ax] = s
        hi = p
        if pooling_convention == "full":
            # ceil-mode (reference pooling.cc `pooling_convention=full`):
            # widen the high-side pad so the last partial window is kept
            size = data.shape[ax]
            out_ceil = -(-(size + 2 * p - k) // s) + 1
            hi = max(p, (out_ceil - 1) * s + k - size - p)
        pads[ax] = (p, hi)

    # init values MUST be python scalars: an array init selects the generic
    # reduce_window primitive, which has no linearization rule under jit
    # (vjp-of-jit is our hybridize backward path)
    if pool_type == "max":
        init = -onp.inf if jnp.issubdtype(data.dtype, jnp.floating) else \
            int(jnp.iinfo(data.dtype).min)
        return lax.reduce_window(data, init, lax.max, window, strides, pads)
    if pool_type in ("avg", "sum"):
        summed = lax.reduce_window(data, 0.0 if jnp.issubdtype(
            data.dtype, jnp.floating) else 0, lax.add, window, strides, pads)
        if pool_type == "sum":
            return summed
        has_extra = any(pads[a][1] > pads[a][0] for a in sp_axes)
        if count_include_pad and not has_extra:
            denom = float(onp.prod(kernel))
            return summed / jnp.asarray(denom, data.dtype)
        # Denominator = valid window elements.  count_include_pad counts the
        # user's padding but NEVER the ceil-mode widening (reference
        # `src/operator/nn/pool.h:468-473` clips the denominator to
        # size+2*pad): pre-pad a ones-mask with the base padding, then let
        # reduce_window's own (zero-contributing) padding cover the extra.
        ones = jnp.ones(data.shape, data.dtype)
        cpads = list(pads)
        if count_include_pad:
            opads = [(0, 0)] * data.ndim
            for ax, p in zip(sp_axes, pad):
                opads[ax] = (p, p)
            ones = jnp.pad(ones, opads, constant_values=1)
            cpads = [(lo - o_lo, hi - o_hi)
                     for (lo, hi), (o_lo, o_hi) in zip(pads, opads)]
        counts = lax.reduce_window(ones, 0.0, lax.add, window, strides, cpads)
        return summed / counts
    if pool_type == "lp":
        p = 2.0
        summed = lax.reduce_window(jnp.abs(data) ** p, 0.0, lax.add,
                                   window, strides, pads)
        return summed ** (1.0 / p)
    raise ValueError(f"unknown pool_type {pool_type!r}")


def adaptive_avg_pool2d(data, output_size, layout="NCHW"):
    """Reference: `src/operator/contrib/adaptive_avg_pooling.cc`."""
    if isinstance(output_size, int):
        output_size = (output_size, output_size)
    h_ax, w_ax = layout.index("H"), layout.index("W")
    h, w = data.shape[h_ax], data.shape[w_ax]
    oh, ow = output_size
    if h % oh == 0 and w % ow == 0:
        return pooling(data, kernel=(h // oh, w // ow), pool_type="avg",
                       stride=(h // oh, w // ow), layout=layout)
    # general case: interpolate bin averages via resize of integral image
    return jax.image.resize(
        data,
        tuple(oh if i == h_ax else ow if i == w_ax else s
              for i, s in enumerate(data.shape)),
        method="linear")


# ---------------------------------------------------------------------------
# normalization (reference: batch_norm.cc, layer_norm.cc, group_norm.cc)
# ---------------------------------------------------------------------------
import functools as _functools


@_functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _bn_train_core(data, gamma, beta, moving_mean, moving_var, momentum,
                   eps, axis):
    out, _res = _bn_train_fwd(data, gamma, beta, moving_mean, moving_var,
                              momentum, eps, axis)
    return out


def _bn_shape(data, axis):
    shape = [1] * data.ndim
    shape[axis] = data.shape[axis]
    return tuple(shape)


def _bn_train_fwd(data, gamma, beta, moving_mean, moving_var, momentum,
                  eps, axis):
    """Single-pass stats (sum, sum-of-squares in f32 — ONE read of the
    activation, two fused reductions) + scale/shift folding: the big
    elementwise op is exactly one multiply-add, which XLA fuses into the
    producing conv's epilogue.  The naive mean/var/normalize chain reads
    the activation three times."""
    red_axes = tuple(i for i in range(data.ndim) if i != axis)
    n = 1
    for i in red_axes:
        n *= data.shape[i]
    cdt = jnp.promote_types(data.dtype, jnp.float32)  # f32 accum; f64 oracle-safe
    xf = data.astype(cdt)
    s1 = jnp.sum(xf, axis=red_axes)
    s2 = jnp.sum(xf * xf, axis=red_axes)
    mean = s1 / n
    var = jnp.maximum(s2 / n - mean * mean, 0.0)
    inv = lax.rsqrt(var + eps)
    a = gamma.astype(cdt) * inv
    b = beta.astype(cdt) - mean * a
    shape = _bn_shape(data, axis)
    out = (xf * a.reshape(shape) + b.reshape(shape)).astype(data.dtype)
    new_mean = moving_mean * momentum + \
        mean.astype(moving_mean.dtype) * (1 - momentum)
    new_var = moving_var * momentum + \
        var.astype(moving_var.dtype) * (1 - momentum)
    return (out, new_mean, new_var), (data, gamma, mean, inv)


def _bn_train_bwd(momentum, eps, axis, res, cts):
    """Hand-written BN backward: sum(dy) and sum(dy*xhat) as two sibling
    reductions, as the forward writes its two, plus one elementwise pass
    that writes dx in the activation's dtype.  XLA merges the siblings
    into one multi-output fusion with their producers (the casts, the
    xhat recompute and, where dy comes out of one, the convolution), so
    dy and the activation are read as the step holds them and neither
    ``dyf`` nor ``xhat`` is written out; the sums accumulate in ``cdt``.
    Under a mesh they are over a sharded axis and the partitioner
    all-reduces them, like the forward's."""
    data, gamma, mean, inv = res
    dy, d_mm, d_mv = cts
    red_axes = tuple(i for i in range(data.ndim) if i != axis)
    n = 1
    for i in red_axes:
        n *= data.shape[i]
    shape = _bn_shape(data, axis)
    cdt = jnp.promote_types(data.dtype, jnp.float32)
    dyf = dy.astype(cdt)
    xhat = (data.astype(cdt) - mean.reshape(shape)) * \
        inv.reshape(shape)
    sum_dy = jnp.sum(dyf, axis=red_axes)
    sum_dy_xhat = jnp.sum(dyf * xhat, axis=red_axes)
    a = (gamma.astype(cdt) * inv).reshape(shape)
    dx = a * (dyf - (sum_dy / n).reshape(shape) -
              xhat * (sum_dy_xhat / n).reshape(shape))
    # moving stats carry stop_gradient semantics w.r.t. data (reference
    # behavior); their cotangents flow only into the old moving buffers
    return (dx.astype(data.dtype), sum_dy_xhat.astype(gamma.dtype),
            sum_dy.astype(gamma.dtype),
            d_mm * momentum, d_mv * momentum)


def _bn_train_fwd_rule(data, gamma, beta, moving_mean, moving_var,
                       momentum, eps, axis):
    outs, res = _bn_train_fwd(data, gamma, beta, moving_mean, moving_var,
                              momentum, eps, axis)
    return outs, res


_bn_train_core.defvjp(_bn_train_fwd_rule, _bn_train_bwd)


def batch_norm_train(data, gamma, beta, momentum, eps, axis, moving_mean,
                     moving_var):
    """Returns (out, new_moving_mean, new_moving_var).

    ``axis`` is canonicalized here: the reduction-axes comprehension in
    `_bn_train_fwd`/`_bn_train_bwd` compares indices literally, and a
    negative axis would silently reduce over EVERY axis (global instead
    of per-channel statistics) and then crash the backward on a scalar
    residual."""
    return _bn_train_core(data, gamma, beta, moving_mean, moving_var,
                          momentum, eps, axis % data.ndim)


def batch_norm_inference(data, gamma, beta, moving_mean, moving_var, eps, axis):
    shape = [1] * data.ndim
    shape[axis] = data.shape[axis]
    inv = lax.rsqrt(moving_var.astype(jnp.float32) + eps).astype(data.dtype)
    return (data - moving_mean.reshape(shape)) * inv.reshape(shape) * \
        gamma.reshape(shape) + beta.reshape(shape)


def layer_norm(data, gamma, beta, axis=-1, eps=1e-5):
    """Single-pass statistics, like `_bn_train_fwd`: sum and
    sum-of-squares in one fused read (promoted accumulation dtype), then
    one multiply-add — the naive mean/var/normalize chain reads the
    activation three times and shows up hard in transformer steps."""
    cdt = jnp.promote_types(data.dtype, jnp.float32)
    xf = data.astype(cdt)
    n = data.shape[axis]
    s1 = jnp.sum(xf, axis=axis, keepdims=True)
    s2 = jnp.sum(xf * xf, axis=axis, keepdims=True)
    mean = s1 / n
    var = jnp.maximum(s2 / n - mean * mean, 0.0)
    inv = lax.rsqrt(var + eps)
    shape = [1] * data.ndim
    ax = axis if axis >= 0 else data.ndim + axis
    shape[ax] = data.shape[ax]
    a = inv * gamma.reshape(shape).astype(cdt)
    b = beta.reshape(shape).astype(cdt) - mean * a
    return (xf * a + b).astype(data.dtype)


def group_norm(data, gamma, beta, num_groups, eps=1e-5):
    """NC+ layout; normalize per (N, group)."""
    n, c = data.shape[0], data.shape[1]
    rest = data.shape[2:]
    x = data.reshape((n, num_groups, c // num_groups) + rest)
    axes = tuple(range(2, x.ndim))
    mean = jnp.mean(x, axis=axes, keepdims=True)
    var = jnp.var(x, axis=axes, keepdims=True)
    x = (x - mean) * lax.rsqrt(var.astype(jnp.float32) + eps).astype(data.dtype)
    x = x.reshape(data.shape)
    shape = [1] * data.ndim
    shape[1] = c
    return x * gamma.reshape(shape) + beta.reshape(shape)


def instance_norm(data, gamma, beta, eps=1e-5):
    axes = tuple(range(2, data.ndim))
    mean = jnp.mean(data, axis=axes, keepdims=True)
    var = jnp.var(data, axis=axes, keepdims=True)
    x = (data - mean) * lax.rsqrt(var.astype(jnp.float32) + eps).astype(data.dtype)
    shape = [1] * data.ndim
    shape[1] = data.shape[1]
    return x * gamma.reshape(shape) + beta.reshape(shape)


def l2_normalization(data, eps=1e-10, mode="instance"):
    if mode == "instance":
        axes = tuple(range(1, data.ndim))
    elif mode == "channel":
        axes = (1,)
    else:  # spatial
        axes = tuple(range(2, data.ndim))
    norm = jnp.sqrt(jnp.sum(jnp.square(data), axis=axes, keepdims=True) + eps)
    return data / norm


# ---------------------------------------------------------------------------
# dense / softmax family (reference: fully_connected.cc, softmax.cc)
# ---------------------------------------------------------------------------
def fully_connected(data, weight, bias=None, num_hidden=None, flatten=True):
    if flatten and data.ndim > 2:
        data = data.reshape((data.shape[0], -1))
    out = jnp.matmul(data, weight.T,
                     preferred_element_type=_acc_type(data.dtype))
    out = out.astype(data.dtype)
    if bias is not None:
        out = out + bias
    return out


def softmax(data, axis=-1, temperature=None, length=None):
    if temperature is not None and temperature != 1.0:
        data = data / temperature
    if length is not None:
        mask = _length_mask(data, length, axis)
        data = jnp.where(mask, data, -jnp.inf)
        out = jax.nn.softmax(data, axis=axis)
        return jnp.where(mask, out, 0)
    return jax.nn.softmax(data, axis=axis)


def log_softmax(data, axis=-1, temperature=None):
    if temperature is not None and temperature != 1.0:
        data = data / temperature
    return jax.nn.log_softmax(data, axis=axis)


def masked_softmax(data, mask, axis=-1, temperature=1.0):
    if temperature != 1.0:
        data = data / temperature
    neg = jnp.asarray(-jnp.inf, data.dtype)
    out = jax.nn.softmax(jnp.where(mask, data, neg), axis=axis)
    return jnp.where(mask, out, 0)


def masked_log_softmax(data, mask, axis=-1, temperature=1.0):
    if temperature != 1.0:
        data = data / temperature
    neg = jnp.asarray(-jnp.inf, data.dtype)
    return jnp.where(mask, jax.nn.log_softmax(
        jnp.where(mask, data, neg), axis=axis), -jnp.inf)


def _length_mask(data, length, axis):
    ax = axis if axis >= 0 else data.ndim + axis
    idx = jnp.arange(data.shape[ax])
    idx = idx.reshape([-1 if i == ax else 1 for i in range(data.ndim)])
    ln = length.reshape([data.shape[0]] + [1] * (data.ndim - 1))
    return idx < ln


# ---------------------------------------------------------------------------
# activations (reference: activation.cc, leaky_relu.cc)
# ---------------------------------------------------------------------------
def activation(data, act_type="relu"):
    table = {
        "relu": jax.nn.relu,
        "sigmoid": jax.nn.sigmoid,
        "log_sigmoid": jax.nn.log_sigmoid,
        "tanh": jnp.tanh,
        "softrelu": jax.nn.softplus,
        "softsign": jax.nn.soft_sign,
        "mish": lambda x: x * jnp.tanh(jax.nn.softplus(x)),
    }
    return table[act_type](data)


_SQRT_HALF = 0.7071067811865476
_INV_SQRT_2PI = 0.3989422804014327


@jax.custom_vjp
def _gelu_erf_f32(data):
    xf = data.astype(jnp.float32)
    return (0.5 * xf * (1.0 + lax.erf(xf * _SQRT_HALF))).astype(data.dtype)


def _gelu_erf_f32_fwd(data):
    # With 11 operations XLA no longer writes GELU's result in a training
    # step: it re-evaluates `erf` in the operands of every consumer (the next
    # matmul, its weight gradient, the backward), which gave back 5.2 of the
    # 6.0 ms a BERT-base step had gained (PERF.md, PR 29).  Behind the barrier
    # the result is a tensor, written once by the epilogue of the matmul that
    # made `data`.  Only here: a barrier that autodiff sees also bars the
    # cotangent, and splits the backward matmul from GELU'.
    return lax.optimization_barrier(_gelu_erf_f32(data)), data


def _gelu_erf_f32_bwd(data, dy):
    xf = data.astype(jnp.float32)
    slope = 0.5 * (1.0 + lax.erf(xf * _SQRT_HALF)) \
        + xf * jnp.exp(-0.5 * xf * xf) * _INV_SQRT_2PI
    return ((dy.astype(jnp.float32) * slope).astype(data.dtype),)


_gelu_erf_f32.defvjp(_gelu_erf_f32_fwd, _gelu_erf_f32_bwd)


def gelu_exact(data):
    """Exact GELU, ``0.5 * x * (1 + erf(x / sqrt(2)))`` -- what
    ``hidden_act: "gelu"`` of ``bert-base-uncased`` computes -- in the form that
    suits the input's dtype, chosen at trace time.

    bf16 and f16: converted to f32 once, the expression above with a native
    ``erf``, rounded back once; the backward keeps the input alone and
    recomputes ``erf`` in f32 (reverse mode only: a ``custom_vjp``).
    ``jax.nn.gelu(approximate=False)`` is ``0.5 * x * erfc(-x * sqrt_half)``,
    which XLA expands into both branches of ``erfc`` (70 elementwise
    operations with two divides and an exponential over the whole tensor: more
    than the FFN matmul's own time in the epilogue it is fused into) and whose
    argument is rounded to the narrow dtype before ``erfc`` sees it, so the
    tail ``erfc`` exists for is lost at its input.  The f32 ``erf`` form is 11
    operations and nearer the true value (tests/test_gelu_exact.py).

    f32 and f64: ``jax.nn.gelu(approximate=False)`` unchanged: there the
    ``erfc`` form's tail is real precision.
    """
    if jnp.issubdtype(data.dtype, jnp.floating) and data.dtype.itemsize < 4:
        return _gelu_erf_f32(data)
    return jax.nn.gelu(data, approximate=False)


def leaky_relu(data, gamma=None, act_type="leaky", slope=0.25,
               lower_bound=0.125, upper_bound=0.334):
    if act_type == "leaky":
        return jnp.where(data >= 0, data, slope * data)
    if act_type == "prelu":
        g = gamma.reshape((1, -1) + (1,) * (data.ndim - 2)) if gamma.ndim == 1 \
            and data.ndim > 2 else gamma
        return jnp.where(data >= 0, data, g * data)
    if act_type == "elu":
        return jnp.where(data >= 0, data, slope * (jnp.exp(data) - 1))
    if act_type == "selu":
        return jax.nn.selu(data)
    if act_type == "gelu":
        return gelu_exact(data)
    if act_type == "gelu_tanh":
        return jax.nn.gelu(data, approximate=True)
    if act_type == "rrelu":
        # inference behavior: use mean slope (reference leaky_relu-inl.h)
        return jnp.where(data >= 0, data,
                         (lower_bound + upper_bound) / 2 * data)
    raise ValueError(f"unknown act_type {act_type!r}")


def dropout(data, key, p=0.5, axes=None, mode="training"):
    """Inverted dropout: keep each slot with probability ``1 - p`` and scale
    the kept ones by ``1 / (1 - p)``; ``axes`` names the axes the mask
    varies over (it is broadcast over the others).  ``p == 0`` and every
    mode but ``"training"`` return ``data`` itself.

    The keep-mask is made once, behind `lax.optimization_barrier`: one
    fusion reads the random words and writes the mask as ``pred``, and every
    consumer, forward and backward, reads that one tensor.  Without the
    barrier XLA repeats the compare in a backward fusion, because a compare
    is cheap (PERF.md, PR 29: it duplicates what is cheap into every
    consumer), and then the words themselves, 4 bytes a slot where 1 was
    already written, are copied to HBM to outlive the forward pass and
    fetched back: in a BERT-base step at batch 128, T=128, 24 buffers of
    50.3 MB, 1.21 GB of its 10.04 GB of temporaries (PERF.md, PR 31; ledger,
    PR 31, `bert_base.phase1_t128`).  The mask is ``pred``, so the barrier
    bars no cotangent; the draw, the compare and the `where` are unchanged
    and so are the bits (tests/test_gluon.py); tests/test_tpu_compile.py
    holds the compiled step to one reader per random tensor."""
    if p == 0.0 or mode != "training":
        return data
    shape = list(data.shape)
    if axes:
        for i in range(len(shape)):
            if i not in axes:
                shape[i] = 1
    keep = 1.0 - p
    mask = lax.optimization_barrier(
        jax.random.bernoulli(_dropout_key(key), keep, tuple(shape)))
    return jnp.where(mask, data / keep, 0).astype(data.dtype)


def _dropout_key(key, impl=None):
    """Dropout mask bits come from the XLA hardware RNG (`rbg`): the
    BULK draw is one RngBitGenerator call where threefry is a chain of
    integer rounds over every mask element.  Chosen on jax 0.4.37 on
    another machine (the pairs are in git history, 1f4c461); this
    machine has no rbg-against-threefry number.  What the words cost
    after the draw is `dropout`'s to hold down (written once as
    ``pred``, PR 31); what is left of the mask path on the chip, the
    attention masks' relayout, is ROADMAP S4 (b).  The key STREAM stays
    threefry (cheap scalar fold_ins); only the per-site key re-wraps.
    Same Bernoulli marginals; bits are backend-stable but differ from
    the threefry stream (``impl="threefry"`` gives those bits back;
    tests pin it).  Reference analogue: dropout uses the cuDNN/GPU
    hardware RNG, not the CPU one (`src/operator/nn/dropout-inl.h`)."""
    if impl is None:
        impl = _DROPOUT_RNG_IMPL
    if impl != "rbg":
        return key
    kd = jax.random.key_data(key).ravel()
    if kd.size >= 4:        # already an rbg-layout key: no re-wrap
        return key
    return jax.random.wrap_key_data(
        jnp.tile(kd, 2)[:4].astype(jnp.uint32), impl="rbg")


# ---------------------------------------------------------------------------
# embedding / indexing helpers (reference: indexing_op.cc)
# ---------------------------------------------------------------------------
def embedding(data, weight, input_dim=None, output_dim=None, dtype=None,
              sparse_grad=False):
    return jnp.take(weight, data.astype(jnp.int32), axis=0)


def one_hot(indices, depth, on_value=1.0, off_value=0.0, dtype="float32"):
    return jax.nn.one_hot(indices.astype(jnp.int32), depth, dtype=dtype) * \
        (on_value - off_value) + off_value


def pick(data, index, axis=-1, keepdims=False, mode="clip"):
    ax = axis if axis >= 0 else data.ndim + axis
    idx = index.astype(jnp.int32)
    if mode == "clip":
        idx = jnp.clip(idx, 0, data.shape[ax] - 1)
    else:
        idx = idx % data.shape[ax]
    picked = jnp.take_along_axis(data, jnp.expand_dims(idx, ax), axis=ax)
    return picked if keepdims else jnp.squeeze(picked, axis=ax)


def topk(data, axis=-1, k=1, ret_typ="indices", is_ascend=False, dtype="float32"):
    ax = axis if axis >= 0 else data.ndim + axis
    x = jnp.moveaxis(data, ax, -1)
    if is_ascend:
        vals, idx = jax.lax.top_k(-x, k)
        vals = -vals
    else:
        vals, idx = jax.lax.top_k(x, k)
    vals = jnp.moveaxis(vals, -1, ax)
    idx = jnp.moveaxis(idx, -1, ax)
    if ret_typ == "indices":
        return idx.astype(dtype)
    if ret_typ == "value":
        return vals
    if ret_typ == "both":
        return vals, idx.astype(dtype)
    if ret_typ == "mask":
        mask = jnp.zeros_like(jnp.moveaxis(data, ax, -1), dtype=dtype)
        mask = jnp.put_along_axis(
            mask, jnp.moveaxis(idx, ax, -1), 1, axis=-1, inplace=False)
        return jnp.moveaxis(mask, -1, ax)
    raise ValueError(f"unknown ret_typ {ret_typ!r}")


def batch_dot(a, b, transpose_a=False, transpose_b=False):
    if transpose_a:
        a = jnp.swapaxes(a, -1, -2)
    if transpose_b:
        b = jnp.swapaxes(b, -1, -2)
    return jnp.matmul(a, b, preferred_element_type=_acc_type(a.dtype)).astype(a.dtype)


# ---------------------------------------------------------------------------
# sequence ops (reference: sequence_mask.cc / _last / _reverse)
# ---------------------------------------------------------------------------
def sequence_mask(data, sequence_length=None, use_sequence_length=False,
                  value=0.0, axis=0):
    if not use_sequence_length or sequence_length is None:
        return data
    # data: (T, N, ...) if axis=0 else (N, T, ...)
    t_ax = axis
    steps = jnp.arange(data.shape[t_ax])
    shape = [1] * data.ndim
    shape[t_ax] = data.shape[t_ax]
    steps = steps.reshape(shape)
    n_ax = 1 - t_ax
    ln_shape = [1] * data.ndim
    ln_shape[n_ax] = data.shape[n_ax]
    ln = sequence_length.reshape(ln_shape)
    return jnp.where(steps < ln, data, jnp.asarray(value, data.dtype))


def sequence_last(data, sequence_length=None, use_sequence_length=False, axis=0):
    if not use_sequence_length or sequence_length is None:
        return jnp.take(data, data.shape[axis] - 1, axis=axis)
    idx = (sequence_length - 1).astype(jnp.int32)
    if axis == 0:
        return data[idx, jnp.arange(data.shape[1])]
    return data[jnp.arange(data.shape[0]), idx]


def sequence_reverse(data, sequence_length=None, use_sequence_length=False,
                     axis=0):
    if not use_sequence_length or sequence_length is None:
        return jnp.flip(data, axis=axis)
    t = data.shape[axis]
    steps = jnp.arange(t)
    ln = sequence_length.astype(jnp.int32)
    # per-sequence reversal index: rev[i] = len-1-i for i<len else i
    idx = jnp.where(steps[None, :] < ln[:, None],
                    ln[:, None] - 1 - steps[None, :], steps[None, :])
    if axis == 0:
        return data[idx.T, jnp.arange(data.shape[1])[None, :]]
    return jnp.take_along_axis(
        data, idx.reshape(idx.shape + (1,) * (data.ndim - 2)), axis=1)


def smooth_l1(data, scalar=1.0):
    s2 = scalar * scalar
    return jnp.where(jnp.abs(data) < 1.0 / s2,
                     0.5 * s2 * jnp.square(data),
                     jnp.abs(data) - 0.5 / s2)


def reshape_like(lhs, rhs):
    return lhs.reshape(rhs.shape)


def gamma_fn(data):
    return jnp.exp(jax.lax.lgamma(data))


def gammaln(data):
    return jax.lax.lgamma(data)


def erf(data):
    return jax.lax.erf(data)


def erfinv(data):
    return jax.lax.erf_inv(data)


def relu(data):
    return jax.nn.relu(data)


def sigmoid(data):
    return jax.nn.sigmoid(data)


def arange_like(data, start=0.0, step=1.0, repeat=1, ctx=None, axis=None):
    if axis is None:
        n = int(onp.prod(data.shape))
        out = start + step * jnp.arange(n, dtype=data.dtype)
        return out.reshape(data.shape)
    n = data.shape[axis]
    return start + step * jnp.arange(n, dtype=data.dtype)
