"""Broad backward sweep: numeric-gradient oracle over the op surface
(VERDICT r1 missing #8).

Reference: `python/mxnet/test_utils.py:1043` check_numeric_gradient is
the backbone oracle applied across `tests/python/unittest/test_operator
.py`; this sweep applies the same oracle to the differentiable core of
mx.np / mx.npx / mx.nd.
"""
import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import ndarray as nd
from mxnet_tpu.test_utils import check_numeric_gradient


def _rand(*shape, lo=-1.0, hi=1.0, seed=0):
    rs = onp.random.RandomState(seed + sum(shape))
    return mx.np.array((rs.rand(*shape) * (hi - lo) + lo).astype("f"))


# (name, fn, input builders) — positive-domain ops get lo>0
UNARY_CASES = [
    ("exp", lambda x: mx.np.exp(x), dict()),
    ("log", lambda x: mx.np.log(x), dict(lo=0.2, hi=3.0)),
    ("sqrt", lambda x: mx.np.sqrt(x), dict(lo=0.2, hi=3.0)),
    ("rsqrt", lambda x: nd.rsqrt(x), dict(lo=0.3, hi=3.0)),
    ("square", lambda x: mx.np.square(x), dict()),
    ("tanh", lambda x: mx.np.tanh(x), dict()),
    ("sigmoid", lambda x: mx.npx.sigmoid(x), dict()),
    ("relu", lambda x: mx.npx.relu(x), dict(lo=0.1, hi=2.0)),
    ("softsign", lambda x: nd.softsign(x), dict()),
    ("erf", lambda x: mx.npx.erf(x), dict()),
    ("abs-shifted", lambda x: mx.np.abs(x + 2.0), dict(lo=0.0, hi=1.0)),
    ("sin", lambda x: mx.np.sin(x), dict()),
    ("arctan", lambda x: mx.np.arctan(x), dict()),
    ("cbrt", lambda x: mx.np.cbrt(x), dict(lo=0.3, hi=2.0)),
    ("expm1", lambda x: mx.np.expm1(x), dict()),
    ("log1p", lambda x: mx.np.log1p(x), dict(lo=0.0, hi=2.0)),
    ("reciprocal", lambda x: nd.reciprocal(x), dict(lo=0.5, hi=2.0)),
    ("softmax", lambda x: mx.npx.softmax(x, axis=-1), dict()),
    ("log_softmax", lambda x: mx.npx.log_softmax(x, axis=-1), dict()),
    ("hard_sigmoid", lambda x: nd.hard_sigmoid(x), dict(lo=-1.5, hi=1.5)),
    ("LRN", lambda x: nd.LRN(x.reshape(1, 4, 2, 1), nsize=3), dict()),
    ("l2_normalization",
     lambda x: mx.npx.l2_normalization(x.reshape(2, 4)),
     dict(lo=0.3, hi=2.0)),
    ("smooth_l1", lambda x: mx.npx.smooth_l1(x), dict(lo=0.2, hi=2.0)),
    ("sum-exclude",
     lambda x: nd.sum(x.reshape(2, 2, 2), axis=1, exclude=True), dict()),
    ("mean", lambda x: mx.np.mean(x), dict()),
    ("norm", lambda x: nd.norm(x), dict(lo=0.3, hi=2.0)),
    ("prod", lambda x: mx.np.prod(x), dict(lo=0.5, hi=1.5)),
    ("cumsum", lambda x: mx.np.cumsum(x), dict()),
    ("max-smooth",
     lambda x: (mx.npx.softmax(x * 3) * x).sum(), dict()),
    ("transpose", lambda x: mx.np.transpose(x.reshape(2, 4)), dict()),
    ("Reshape-codes",
     lambda x: nd.Reshape(x.reshape(2, 2, 2), shape=(0, -1)), dict()),
    ("slice",
     lambda x: nd.slice(x.reshape(2, 4), begin=(0, 1), end=(2, 3)), dict()),
    ("tile", lambda x: mx.np.tile(x, 2), dict()),
    ("clip-interior", lambda x: nd.clip(x, -10.0, 10.0), dict()),
    ("pad",
     lambda x: nd.Pad(x.reshape(1, 1, 2, 4), mode="constant",
                      pad_width=(0, 0, 0, 0, 1, 1, 1, 1)), dict()),
    ("depth_to_space",
     lambda x: nd.depth_to_space(x.reshape(1, 4, 1, 2), 2), dict()),
    ("gamma-ln", lambda x: mx.npx.gammaln(x), dict(lo=0.5, hi=3.0)),
]


@pytest.mark.parametrize("name,fn,dom", UNARY_CASES,
                         ids=[c[0] for c in UNARY_CASES])
def test_unary_numeric_gradient(name, fn, dom):
    x = _rand(8, **dom)
    check_numeric_gradient(fn, [x])


BINARY_CASES = [
    ("broadcast_add", lambda a, b: nd.broadcast_add(a, b)),
    ("broadcast_mul", lambda a, b: nd.broadcast_mul(a, b)),
    ("broadcast_div", lambda a, b: nd.broadcast_div(a + 2.5, b + 2.5)),
    ("broadcast_maximum-offset",
     lambda a, b: nd.broadcast_maximum(a + 3.0, b)),
    ("hypot", lambda a, b: nd.broadcast_hypot(a + 2.0, b + 2.0)),
    ("dot", lambda a, b: nd.dot(a.reshape(2, 4), b.reshape(4, 2))),
    ("batch_dot",
     lambda a, b: mx.npx.batch_dot(a.reshape(2, 2, 2), b.reshape(2, 2, 2))),
    ("where-fixed",
     lambda a, b: nd.where(mx.np.array([1.0, 0, 1, 0, 1, 0, 1, 0]), a, b)),
    ("matmul", lambda a, b: mx.np.matmul(a.reshape(2, 4), b.reshape(4, 2))),
    ("power", lambda a, b: nd.broadcast_power(a + 2.0, b + 2.0)),
]


@pytest.mark.parametrize("name,fn", BINARY_CASES,
                         ids=[c[0] for c in BINARY_CASES])
def test_binary_numeric_gradient(name, fn):
    a = _rand(8, seed=1)
    b = _rand(8, seed=2)
    check_numeric_gradient(fn, [a, b])


def test_layer_ops_numeric_gradient():
    rs = onp.random.RandomState(0)
    x = mx.np.array(rs.rand(2, 3, 6, 6).astype("f"))
    w = mx.np.array((rs.rand(4, 3, 3, 3) * 0.5).astype("f"))
    b = mx.np.array(rs.rand(4).astype("f"))
    check_numeric_gradient(
        lambda x, w, b: nd.Convolution(x, w, b, kernel=(3, 3), num_filter=4),
        [x, w, b], rtol=2e-2, atol=2e-3)

    d = mx.np.array(rs.rand(4, 6).astype("f"))
    fw = mx.np.array((rs.rand(3, 6) * 0.5).astype("f"))
    fb = mx.np.array(rs.rand(3).astype("f"))
    check_numeric_gradient(
        lambda d, w, b: nd.FullyConnected(d, w, b, num_hidden=3),
        [d, fw, fb])

    g = mx.np.array(onp.ones(3, "f"))
    beta = mx.np.array(onp.zeros(3, "f"))
    check_numeric_gradient(
        lambda x, g, b: mx.npx.layer_norm(x, g, b, axis=-1),
        [mx.np.array(rs.rand(4, 3).astype("f")), g, beta],
        rtol=2e-2, atol=2e-3)

    # pooling through avg (max is kink-free only off ties)
    check_numeric_gradient(
        lambda x: nd.Pooling(x, kernel=(2, 2), stride=(2, 2),
                             pool_type="avg"),
        [mx.np.array(rs.rand(1, 2, 4, 4).astype("f"))])


def test_embedding_and_take_numeric_gradient():
    rs = onp.random.RandomState(4)
    w = mx.np.array(rs.rand(5, 3).astype("f"))
    idx = mx.np.array(onp.array([0, 2, 4, 2]), dtype="int32")
    check_numeric_gradient(
        lambda w: mx.npx.embedding(idx, w, input_dim=5, output_dim=3), [w])
    check_numeric_gradient(lambda w: nd.take(w, idx, axis=0), [w])


def test_batch_norm_train_numeric_gradient():
    """The hand-written single-pass BN VJP (ops/nn.py _bn_train_core) vs
    finite differences and the naive mean/var formulation."""
    import jax.numpy as jnp

    from mxnet_tpu.ops import nn as _nn
    from mxnet_tpu.ops.invoke import invoke

    rs = onp.random.RandomState(9)
    x = mx.np.array(rs.rand(4, 3, 5, 5).astype("f") * 2 - 1)
    g = mx.np.array((rs.rand(3) + 0.5).astype("f"))
    b = mx.np.array(rs.rand(3).astype("f"))
    mm = onp.zeros(3, "f")
    mv = onp.ones(3, "f")

    def fn(x, g, b):
        out = invoke(_nn.batch_norm_train,
                     (x, g, b, 0.9, 1e-5, 1, mx.np.array(mm),
                      mx.np.array(mv)), name="bn")
        return out[0]

    check_numeric_gradient(fn, [x, g, b], rtol=2e-2, atol=2e-3)

    # forward + moving stats match the naive formulation
    out, nm, nv = _nn.batch_norm_train(
        x._data, g._data, b._data, 0.9, 1e-5, 1,
        jnp.asarray(mm), jnp.asarray(mv))
    xf = onp.asarray(x._data)
    mean = xf.mean(axis=(0, 2, 3))
    var = xf.var(axis=(0, 2, 3))
    ref = (xf - mean.reshape(1, 3, 1, 1)) / onp.sqrt(
        var.reshape(1, 3, 1, 1) + 1e-5) * onp.asarray(g._data).reshape(
        1, 3, 1, 1) + onp.asarray(b._data).reshape(1, 3, 1, 1)
    onp.testing.assert_allclose(onp.asarray(out), ref, rtol=2e-4, atol=2e-5)
    onp.testing.assert_allclose(onp.asarray(nm), 0.1 * mean, rtol=1e-4)
    onp.testing.assert_allclose(onp.asarray(nv), 0.9 + 0.1 * var, rtol=1e-4)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("axis", [1, -1])
def test_batch_norm_train_backward_matches_plain_f32(axis, dtype):
    """`_bn_train_bwd`'s (dx, dgamma, dbeta) against `jax.grad` of a plain
    f32 mean/var/normalize BatchNorm over the same values: bf16 operands are
    promoted to f32 before the multiply and summed in f32, so only dx's
    final cast separates the two dtypes."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops import nn as _nn

    rs = onp.random.RandomState(11)
    shape = (8, 6, 5, 7) if axis == 1 else (8, 5, 7, 6)
    c = shape[axis]
    x = jnp.asarray(rs.randn(*shape).astype("f") * 1.5 + 0.3, dtype)
    w = jnp.asarray(rs.randn(*shape).astype("f"), dtype)   # dy
    g = jnp.asarray((rs.rand(c) + 0.5).astype("f"))
    b = jnp.asarray(rs.rand(c).astype("f"))
    mm, mv = jnp.zeros(c), jnp.ones(c)

    def ours(x, g, b):
        out, _, _ = _nn.batch_norm_train(x, g, b, 0.9, 1e-5, axis, mm, mv)
        return jnp.sum(out.astype(jnp.float32) * w.astype(jnp.float32))

    def plain(x, g, b):
        red = tuple(i for i in range(x.ndim) if i != axis % x.ndim)
        mean = jnp.mean(x, axis=red, keepdims=True)
        var = jnp.mean(jnp.square(x - mean), axis=red, keepdims=True)
        bshape = mean.shape
        out = (x - mean) / jnp.sqrt(var + 1e-5) * g.reshape(bshape) + \
            b.reshape(bshape)
        return jnp.sum(out * w.astype(jnp.float32))

    got = jax.grad(ours, argnums=(0, 1, 2))(x, g, b)
    want = jax.grad(plain, argnums=(0, 1, 2))(x.astype(jnp.float32), g, b)
    assert got[0].dtype == x.dtype and got[1].dtype == got[2].dtype == g.dtype
    # the forward test's tolerances; dx in bf16, rounded once at the end,
    # the numeric-gradient test's.  The two sums are f32 in both dtypes: a
    # bf16 accumulation would miss the tight pair
    tight, loose = (2e-4, 2e-5), (2e-2, 2e-3)
    for name, a, e in zip(("dx", "dgamma", "dbeta"), got, want):
        rtol, atol = loose if (name, dtype) == ("dx", "bfloat16") else tight
        onp.testing.assert_allclose(onp.asarray(a.astype(jnp.float32)),
                                    onp.asarray(e), rtol=rtol, atol=atol,
                                    err_msg=name)
