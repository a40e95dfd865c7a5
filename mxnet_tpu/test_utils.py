"""Test oracles.

Reference: `python/mxnet/test_utils.py` (2.6k LoC) — the backbone of the
reference test suite: `assert_almost_equal` (:655), `check_numeric_gradient`
finite differences vs autograd (:1043), `check_consistency` cross-context
(:1490), `rand_ndarray` (:484), `default_context` (:57).
"""
from __future__ import annotations

import jax
import numpy as onp

from .context import Context, current_context, cpu
from .ndarray.ndarray import NDArray
from . import numpy as mxnp
from . import autograd

__all__ = [
    "default_context", "set_default_context", "rand_ndarray", "rand_shape_nd",
    "assert_almost_equal", "almost_equal", "same", "check_numeric_gradient",
    "check_consistency", "default_dtype", "effective_dtype",
    "check_symbolic_forward", "check_symbolic_backward",
]

_rng = onp.random.RandomState(12345)


def default_context():
    return current_context()


def set_default_context(ctx):
    Context._default_ctx = Context(ctx)


def default_dtype():
    return onp.float32


def effective_dtype(dat):
    """Tolerance class for a dtype (bf16/f16 are coarse on TPU MXU)."""
    dt = onp.dtype(dat.dtype) if hasattr(dat, "dtype") else onp.float32
    return dt


_DTOL = {onp.dtype(onp.float16): 1e-2, onp.dtype(onp.float32): 1e-4,
         onp.dtype(onp.float64): 1e-6}
_DEFAULT_RTOL = {onp.dtype(onp.float16): 1e-2, onp.dtype(onp.float32): 1e-4,
                 onp.dtype(onp.float64): 1e-5}


def _to_numpy(a):
    if isinstance(a, NDArray):
        return a.asnumpy()
    return onp.asarray(a)


def same(a, b):
    return onp.array_equal(_to_numpy(a), _to_numpy(b))


def almost_equal(a, b, rtol=None, atol=None, equal_nan=False):
    a, b = _to_numpy(a), _to_numpy(b)
    rtol = rtol if rtol is not None else _DEFAULT_RTOL.get(a.dtype, 1e-4)
    atol = atol if atol is not None else _DTOL.get(a.dtype, 1e-5)
    return onp.allclose(a, b, rtol=rtol, atol=atol, equal_nan=equal_nan)


def assert_almost_equal(a, b, rtol=None, atol=None, names=("a", "b"),
                        equal_nan=False):
    """Reference: `test_utils.py:655` (tolerance defaults keyed by dtype)."""
    a_np, b_np = _to_numpy(a), _to_numpy(b)
    if a_np.dtype == onp.dtype("bfloat16") if hasattr(onp, "bfloat16") else False:
        a_np = a_np.astype(onp.float32)
    a_np = onp.asarray(a_np, dtype=onp.float64 if a_np.dtype.kind == "f" else a_np.dtype)
    b_np = onp.asarray(b_np, dtype=onp.float64 if b_np.dtype.kind == "f" else b_np.dtype)
    rtol = rtol if rtol is not None else 1e-4
    atol = atol if atol is not None else 1e-5
    if not onp.allclose(a_np, b_np, rtol=rtol, atol=atol, equal_nan=equal_nan):
        err = onp.abs(a_np - b_np)
        rel = err / (onp.abs(b_np) + atol)
        idx = onp.unravel_index(onp.argmax(rel), rel.shape) if rel.size else ()
        raise AssertionError(
            f"Arrays {names[0]} and {names[1]} not almost equal "
            f"(rtol={rtol}, atol={atol}); max abs err "
            f"{err.max() if err.size else 0:.3e}, max rel err "
            f"{rel.max() if rel.size else 0:.3e} at {idx};\n"
            f"{names[0]}: {a_np.flat[:8]}...\n{names[1]}: {b_np.flat[:8]}..."
        )


def rand_shape_nd(ndim, dim=10, allow_zero_size=False):
    low = 0 if allow_zero_size else 1
    return tuple(_rng.randint(low, dim + 1, size=ndim))


def rand_ndarray(shape, density=1.0, dtype=None, ctx=None,
                 distribution="uniform"):
    """Reference: `test_utils.py:484` (sparse variants collapse to dense —
    XLA has no sparse buffers)."""
    dtype = dtype or onp.float32
    if distribution == "uniform":
        arr = _rng.uniform(-1.0, 1.0, size=shape)
    elif distribution == "normal":
        arr = _rng.normal(size=shape)
    elif distribution == "powerlaw":
        arr = _rng.power(2, size=shape)
    else:
        raise ValueError(f"unknown distribution {distribution!r}")
    if density < 1.0:
        mask = _rng.binomial(1, density, size=shape)
        arr = arr * mask
    return mxnp.array(arr.astype(dtype), ctx=ctx)


def check_numeric_gradient(f, inputs, eps=1e-3, rtol=1e-2, atol=1e-3,
                           grad_nodes=None):
    """Finite differences vs autograd (reference `test_utils.py:1043`).

    ``f(*inputs) -> NDArray scalar-or-array`` built from mx ops; ``inputs``
    are NDArrays.  Compares d(sum(f))/dx computed by the tape against central
    differences.
    """
    inputs = list(inputs)
    for x in inputs:
        x.attach_grad()
    with autograd.record():
        out = f(*inputs)
        loss = out.sum()
    loss.backward()
    analytic = [x.grad.asnumpy().astype(onp.float64) for x in inputs]

    # The numeric oracle runs in float64 (enable_x64 scope): float32 XLA
    # kernels have pointwise error ~4e-5 which the 1/(2*eps) division would
    # amplify past any reasonable tolerance.
    originals = [x._data for x in inputs]
    try:
        with jax.enable_x64():
            for x in inputs:
                # promote real-valued inputs (incl. bf16, numpy kind 'V');
                # int/bool/unsigned index inputs keep their dtype
                if x.dtype.kind not in "iub":
                    x._rebind(mxnp.array(
                        x.asnumpy().astype(onp.float64))._data)
            for i, x in enumerate(inputs):
                if grad_nodes is not None and i not in grad_nodes:
                    continue
                base = onp.ascontiguousarray(x.asnumpy().astype(onp.float64))
                num = onp.zeros_like(base)
                for idx in onp.ndindex(base.shape):
                    orig = base[idx]
                    base[idx] = orig + eps
                    x._rebind(mxnp.array(base)._data)
                    fp = f(*inputs).sum().asnumpy().astype(onp.float64)
                    base[idx] = orig - eps
                    x._rebind(mxnp.array(base)._data)
                    fm = f(*inputs).sum().asnumpy().astype(onp.float64)
                    base[idx] = orig
                    x._rebind(mxnp.array(base)._data)
                    num[idx] = (fp - fm) / (2 * eps)
                assert_almost_equal(analytic[i], num, rtol=rtol, atol=atol,
                                    names=(f"autograd[{i}]", f"numeric[{i}]"))
    finally:
        for x, d in zip(inputs, originals):
            x._rebind(d)


def check_consistency(f, inputs, ctx_list=None, rtol=1e-4, atol=1e-5):
    """Run ``f`` on multiple contexts and cross-compare (reference
    `test_utils.py:1490`, the CPU↔GPU oracle — here CPU↔TPU)."""
    if ctx_list is None:
        from .context import cpu, num_tpus, tpu
        ctx_list = [cpu()] + ([tpu()] if num_tpus() else [])
    results = []
    for ctx in ctx_list:
        moved = [x.as_in_ctx(ctx) for x in inputs]
        results.append(_to_numpy(f(*moved)))
    for r in results[1:]:
        assert_almost_equal(results[0], r, rtol=rtol, atol=atol,
                            names=(str(ctx_list[0]), "other"))
    return results


def check_symbolic_forward(sym, inputs, expected, rtol=None, atol=None):
    """Bind ``sym`` to ``inputs`` (list ordered by ``list_arguments``) and
    compare outputs to ``expected`` numpy arrays (reference
    `test_utils.py:1193`)."""
    names = sym.list_arguments()
    assert len(names) == len(inputs), (names, len(inputs))
    ex = sym.bind(args=dict(zip(names, inputs)))
    outs = ex.forward()
    assert len(outs) == len(expected), (len(outs), len(expected))
    for i, (o, e) in enumerate(zip(outs, expected)):
        assert_almost_equal(_to_numpy(o), _to_numpy(e), rtol=rtol, atol=atol,
                            names=(f"output[{i}]", f"expected[{i}]"))
    return outs


def check_symbolic_backward(sym, inputs, out_grads, expected, rtol=None,
                            atol=None):
    """Bind, forward, backward with ``out_grads`` cotangents, and compare
    input gradients to ``expected`` (reference `test_utils.py:1276`)."""
    names = sym.list_arguments()
    ex = sym.bind(args=dict(zip(names, inputs)))
    ex.forward()
    grads = ex.backward(out_grads)
    assert len(grads) == len(expected), (len(grads), len(expected))
    for n, g, e in zip(names, grads, expected):
        assert_almost_equal(_to_numpy(g), _to_numpy(e), rtol=rtol, atol=atol,
                            names=(f"grad[{n}]", f"expected[{n}]"))
    return grads
