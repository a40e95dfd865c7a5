"""Exact GELU (`ops/nn.py::gelu_exact`) against float64, over every finite
value of the narrow dtype in [-12, 12]: the f32-`erf` form that bf16 and f16
inputs take must be the same function as `jax.nn.gelu(approximate=False)`,
and nearer the true value; f32 and f64 inputs keep jax's own form, bit for bit.
"""
import math

import jax
import jax.numpy as jnp
import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu.ops import nn as nn_ops


def _old(x):
    return jax.nn.gelu(x, approximate=False)


def _all_finite(dtype, bound=12.0):
    """Every finite value of a 16-bit float dtype in [-bound, bound]."""
    x = jnp.arange(2 ** 16, dtype=jnp.uint16).view(dtype)
    return x[onp.asarray(jnp.abs(x.astype(jnp.float32)) <= bound)]   # NaN: False


def _truth(x64, quantity):
    """float64 `0.5*x*erfc(-x/sqrt(2))`, or its derivative `Phi(x) + x*phi(x)`."""
    cdf = 0.5 * onp.vectorize(math.erfc)(-x64 / math.sqrt(2.0))
    if quantity == "value":
        return x64 * cdf
    return cdf + x64 * onp.exp(-0.5 * x64 * x64) / math.sqrt(2.0 * math.pi)


def _compute(fn, x, quantity):
    if quantity == "derivative":
        out = jax.grad(lambda v: fn(v).astype(jnp.float32).sum())(x)
    else:
        out = fn(x)
    assert out.dtype == x.dtype
    return out


@pytest.mark.parametrize("quantity", ["value", "derivative"])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float16],
                         ids=["bf16", "f16"])
def test_narrow_gelu_is_no_worse_than_the_erfc_form(dtype, quantity):
    x = _all_finite(dtype)
    assert x.size > 30000
    x64 = onp.asarray(x.astype(jnp.float32), onp.float64)
    truth = _truth(x64, quantity)
    rounded = onp.asarray(jnp.asarray(truth, jnp.float32).astype(dtype)
                          .astype(jnp.float32), onp.float64)

    def errors(fn):
        got = onp.asarray(_compute(fn, x, quantity).astype(jnp.float32),
                          onp.float64)
        assert onp.isfinite(got).all()
        err = onp.abs(got - truth)
        return err, float(onp.mean(got != rounded))

    new_err, new_misrounded = errors(nn_ops.gelu_exact)
    old_err, old_misrounded = errors(_old)
    assert new_err.mean() <= old_err.mean()
    assert new_err.max() <= old_err.max()
    assert new_misrounded <= old_misrounded
    # past |x| = 5 the f32 `erf` has saturated: the value is x or (-)0 within
    # 4e-6; the derivative is within 7.1e-6 of 0 or 1, which is what it rounds to
    tail = {"value": 4e-6, "derivative": 8e-6}[quantity]
    assert new_err[onp.abs(x64) > 5].max() < tail


@pytest.mark.parametrize("dtype", ["float32", "float64"])
def test_wide_gelu_is_jax_gelu_bit_for_bit(dtype):
    with jax.enable_x64():
        x = jnp.concatenate([jnp.linspace(-12, 12, 20001),
                             _all_finite(jnp.bfloat16).astype(jnp.float32)]
                            ).astype(dtype)
        assert x.dtype == dtype
        for fn in (lambda f: f, lambda f: jax.grad(lambda v: f(v).sum())):
            new, old = fn(nn_ops.gelu_exact)(x), fn(_old)(x)
            assert new.dtype == old.dtype == x.dtype
            onp.testing.assert_array_equal(onp.asarray(new), onp.asarray(old))


@pytest.mark.parametrize("via", ["npx.gelu", "npx.leaky_relu", "nn.GELU",
                                 "gelu_tanh"])
def test_every_front_end_reaches_it(via):
    """`npx.gelu`, `npx.leaky_relu(act_type="gelu")` and `nn.GELU()` give the
    f32-`erf` form on bf16; the tanh form stays what `gelu_tanh` asks for."""
    x = mx.np.array(onp.linspace(-6, 6, 4097), dtype="bfloat16")
    got, want = {
        "npx.gelu": (lambda: mx.npx.gelu(x), nn_ops.gelu_exact),
        "npx.leaky_relu": (lambda: mx.npx.leaky_relu(x, act_type="gelu"),
                           nn_ops.gelu_exact),
        "nn.GELU": (lambda: mx.gluon.nn.GELU()(x), nn_ops.gelu_exact),
        "gelu_tanh": (lambda: mx.npx.leaky_relu(x, act_type="gelu_tanh"),
                      lambda d: jax.nn.gelu(d, approximate=True)),
    }[via]
    got, want = got(), want(x._data)
    assert got.dtype == x.dtype
    onp.testing.assert_array_equal(onp.asarray(got._data.astype(jnp.float32)),
                                   onp.asarray(want.astype(jnp.float32)))
