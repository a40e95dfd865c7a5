"""Autograd semantics (reference: tests/python/unittest/test_autograd.py)."""
import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd
from mxnet_tpu.test_utils import assert_almost_equal, check_numeric_gradient


def test_basic_backward():
    x = mx.np.array([1.0, 2.0, 3.0])
    x.attach_grad()
    with autograd.record():
        y = (x * x).sum()
    y.backward()
    assert_almost_equal(x.grad, 2 * x.asnumpy())


def test_chain():
    x = mx.np.array([0.5, 1.0])
    x.attach_grad()
    with autograd.record():
        y = mx.np.exp(mx.np.sin(x)).sum()
    y.backward()
    expected = onp.exp(onp.sin(x.asnumpy())) * onp.cos(x.asnumpy())
    assert_almost_equal(x.grad, expected, rtol=1e-5, atol=1e-6)


def test_multiple_inputs():
    a = mx.np.array([1.0, 2.0])
    b = mx.np.array([3.0, 4.0])
    a.attach_grad()
    b.attach_grad()
    with autograd.record():
        c = (a * b + a).sum()
    c.backward()
    assert_almost_equal(a.grad, b.asnumpy() + 1)
    assert_almost_equal(b.grad, a.asnumpy())


def test_grad_req_add():
    x = mx.np.array([1.0, 2.0])
    x.attach_grad(grad_req="add")
    for _ in range(2):
        with autograd.record():
            y = (x * x).sum()
        y.backward()
    assert_almost_equal(x.grad, 4 * x.asnumpy())
    x.zero_grad()
    assert x.grad.asnumpy().tolist() == [0, 0]


def test_grad_req_write_overwrites():
    x = mx.np.array([1.0, 2.0])
    x.attach_grad()  # write
    for _ in range(2):
        with autograd.record():
            y = (x * x).sum()
        y.backward()
    assert_almost_equal(x.grad, 2 * x.asnumpy())


def test_detach_stops_gradient():
    x = mx.np.array([2.0])
    x.attach_grad()
    with autograd.record():
        y = x * 3
        z = y.detach() * x
    z.backward()
    assert_almost_equal(x.grad, onp.array([6.0]))  # only through second factor


def test_pause():
    x = mx.np.array([1.0])
    x.attach_grad()
    with autograd.record():
        y = x * 2
        with autograd.pause():
            z = x * 10  # not recorded
        w = y + z.detach()
    w.backward()
    assert_almost_equal(x.grad, onp.array([2.0]))


def test_is_recording_training():
    assert not autograd.is_recording()
    with autograd.record():
        assert autograd.is_recording()
        assert autograd.is_training()
    with autograd.record(train_mode=False):
        assert not autograd.is_training()
    with autograd.train_mode():
        assert autograd.is_training()


def test_autograd_grad_api():
    x = mx.np.array([1.0, 2.0])
    x.attach_grad()
    with autograd.record():
        y = (x ** 3).sum()
    (gx,) = autograd.grad(y, [x])
    assert_almost_equal(gx, 3 * x.asnumpy() ** 2)
    # .grad untouched by autograd.grad
    assert x.grad.asnumpy().tolist() == [0, 0]


def test_head_grads():
    x = mx.np.array([1.0, 2.0])
    x.attach_grad()
    with autograd.record():
        y = x * 2
    y.backward(mx.np.array([1.0, 10.0]))
    assert_almost_equal(x.grad, onp.array([2.0, 20.0]))


def test_retain_graph():
    x = mx.np.array([3.0])
    x.attach_grad()
    with autograd.record():
        y = x * x
    y.backward(retain_graph=True)
    y.backward()
    with pytest.raises(RuntimeError):
        y.backward()


def test_higher_order_grad():
    x = mx.np.array([1.0, 2.0])
    x.attach_grad()
    with autograd.record():
        y = (x ** 3).sum()
        (gx,) = autograd.grad(y, [x], create_graph=True, retain_graph=True)
        gsum = gx.sum()
    gsum.backward()
    assert_almost_equal(x.grad, 6 * x.asnumpy())  # d2/dx2 x^3 = 6x


def test_inplace_inside_record():
    """Mutation during recording is tape-safe (snapshot semantics)."""
    x = mx.np.array([1.0, 2.0])
    x.attach_grad()
    with autograd.record():
        y = x * 2     # uses x@v0
        x += 1        # mutates; y's history must be unaffected
        z = (y * x).sum()   # uses x@v1 = x+1
    z.backward()
    # dz/dx = d/dx0 (2*x0*(x0+1)) = 4x0+2  -> via both paths
    assert_almost_equal(x.grad, 4 * onp.array([1.0, 2.0]) + 2)


def test_mark_variables():
    x = mx.np.array([2.0])
    g = mx.np.zeros((1,))
    autograd.mark_variables([x], [g])
    with autograd.record():
        y = x * 5
    y.backward()
    assert_almost_equal(g, onp.array([5.0]))


def test_custom_function():
    class MySigmoid(autograd.Function):
        def forward(self, x):
            y = 1 / (1 + mx.np.exp(-x))
            self.save_for_backward(y)
            return y

        def backward(self, dy):
            (y,) = self.saved_tensors
            return dy * y * (1 - y)

    x = mx.np.array([0.0, 1.0])
    x.attach_grad()
    f = MySigmoid()
    with autograd.record():
        y = f(x).sum()
    y.backward()
    s = 1 / (1 + onp.exp(-x.asnumpy()))
    assert_almost_equal(x.grad, s * (1 - s), rtol=1e-5, atol=1e-6)


def test_numeric_gradient():
    x = mx.np.random.normal(0, 1, (3, 2))
    check_numeric_gradient(lambda a: mx.np.tanh(a * 2), [x])


def test_nondiff_passthrough():
    x = mx.np.array([3.0, 1.0, 2.0])
    x.attach_grad()
    with autograd.record():
        idx = mx.np.argmax(x)  # non-differentiable, should not break
        y = (x * 2).sum()
    y.backward()
    assert_almost_equal(x.grad, onp.full(3, 2.0))


def test_a_gradient_buffer_holds_no_array_until_it_is_read():
    """A buffer that a backward pass writes, or that nothing touches (a
    compiled step keeps its gradients inside), never makes its zeros; one
    that is read first gives zeros, and ``grad_req='add'`` adds to them."""
    import jax.numpy as jnp
    a, b, c = (mx.nd.array(onp.ones((3, 4))) for _ in range(3))
    for x in (a, b, c):
        x.attach_grad()
    assert all(x.grad._buf is None for x in (a, b, c))
    assert c.grad.shape == (3, 4) and c.grad.dtype == onp.float32
    assert c.grad._buf is None                     # shape and dtype read none
    with mx.autograd.record():
        y = (a * 3.0).sum()
    y.backward()
    onp.testing.assert_allclose(a.grad.asnumpy(), 3.0)
    assert b.grad._buf is None
    b.attach_grad(grad_req="add")
    for _ in range(2):
        with mx.autograd.record():
            z = (b * 2.0).sum()
        z.backward()
    onp.testing.assert_allclose(b.grad.asnumpy(), 4.0)
    assert not c.grad.asnumpy().any() and c.grad._buf is not None
    h = mx.nd.array(onp.ones(5), dtype=jnp.bfloat16)
    h.attach_grad()
    assert h.grad.dtype == jnp.bfloat16 and h.grad.asnumpy().dtype == jnp.bfloat16
