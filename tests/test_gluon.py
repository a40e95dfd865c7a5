"""Gluon blocks (reference: tests/python/unittest/test_gluon.py)."""
import jax
import jax.numpy as jnp
import numpy as onp
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import mxnet_tpu as mx
from mxnet_tpu import autograd
from mxnet_tpu.gluon import nn, Parameter, Trainer, loss as gloss
from mxnet_tpu.gluon.parameter import DeferredInitializationError
from mxnet_tpu.ops import nn as nn_ops
from mxnet_tpu.test_utils import assert_almost_equal


def test_parameter_basic():
    p = Parameter("weight", shape=(3, 4))
    p.initialize(init=mx.init.One())
    assert p.data().shape == (3, 4)
    assert p.data().asnumpy().sum() == 12
    assert p.grad().shape == (3, 4)
    assert p.list_ctx() == [mx.current_context()]


def test_parameter_deferred():
    p = Parameter("weight", shape=(4, 0), allow_deferred_init=True)
    p.initialize()
    with pytest.raises(DeferredInitializationError):
        p.data()
    p.shape = (4, 7)
    p.finish_deferred_init()
    assert p.data().shape == (4, 7)


def test_dense_shapes():
    net = nn.Dense(5)
    net.initialize()
    x = mx.np.ones((2, 3))
    out = net(x)
    assert out.shape == (2, 5)
    assert net.weight.shape == (5, 3)
    # flatten semantics
    net2 = nn.Dense(4, flatten=True)
    net2.initialize()
    assert net2(mx.np.ones((2, 3, 5))).shape == (2, 4)
    net3 = nn.Dense(4, flatten=False)
    net3.initialize()
    assert net3(mx.np.ones((2, 3, 5))).shape == (2, 3, 4)


def test_collect_params_names():
    net = nn.HybridSequential()
    net.add(nn.Dense(3), nn.Dense(2))
    params = net.collect_params()
    assert set(params) == {"0.weight", "0.bias", "1.weight", "1.bias"}


def test_save_load_parameters(tmp_path):
    net = nn.HybridSequential()
    net.add(nn.Dense(4, in_units=3), nn.Dense(2, in_units=4))
    net.initialize()
    fname = str(tmp_path / "net.params")
    net.save_parameters(fname)
    net2 = nn.HybridSequential()
    net2.add(nn.Dense(4, in_units=3), nn.Dense(2, in_units=4))
    net2.load_parameters(fname)
    x = mx.np.ones((1, 3))
    assert_almost_equal(net(x), net2(x))


def test_hybridize_matches_eager():
    net = nn.HybridSequential()
    net.add(nn.Dense(8, activation="relu"), nn.Dense(3))
    net.initialize()
    x = mx.np.random.normal(0, 1, (4, 5))
    eager = net(x).asnumpy()
    net.hybridize()
    compiled = net(x).asnumpy()
    assert_almost_equal(eager, compiled, rtol=1e-5, atol=1e-6)
    # second call hits the jit cache
    assert_almost_equal(net(x).asnumpy(), compiled, rtol=1e-5, atol=1e-6)


def test_hybridize_backward():
    net = nn.Dense(1, in_units=2)
    net.initialize(mx.init.One())
    net.hybridize()
    x = mx.np.array([[1.0, 2.0]])
    with autograd.record():
        y = net(x).sum()
    y.backward()
    assert_almost_equal(net.weight.grad(), x.asnumpy())
    assert_almost_equal(net.bias.grad(), onp.array([1.0]))


def test_batchnorm_train_eval():
    bn = nn.BatchNorm(in_channels=3)
    bn.initialize()
    x = mx.np.random.normal(0, 1, (8, 3, 4, 4))
    with autograd.record():
        out_train = bn(x)
    # running stats must have moved toward batch stats
    rm = bn.running_mean.data().asnumpy()
    assert onp.abs(rm).sum() > 0
    out_eval = bn(x)
    assert out_eval.shape == x.shape


def test_batchnorm_negative_axis_per_channel_stats():
    """axis=-1 must normalize per channel, not globally: the reduction
    comprehension compared raw indices, so a negative axis silently
    reduced over EVERY axis (wrong statistics) and crashed backward on
    the scalar residual (round-4 regression, found via npx.remat)."""
    bn = nn.BatchNorm(axis=-1, in_channels=8)
    bn.initialize()
    x = mx.np.array(onp.random.randn(4, 8).astype("float32"))
    x.attach_grad()
    with autograd.record():
        y = bn(x)
        loss = y.sum()
    loss.backward()
    xa = x.asnumpy()
    ref = (xa - xa.mean(0)) / onp.sqrt(xa.var(0) + 1e-5)
    assert onp.abs(y.asnumpy() - ref).max() < 1e-5
    assert onp.isfinite(x.grad.asnumpy()).all()


def test_dropout_train_vs_eval():
    do = nn.Dropout(0.5)
    x = mx.np.ones((100,))
    with autograd.record():
        out_train = do(x)
    out_eval = do(x)
    assert (out_eval.asnumpy() == 1).all()
    assert (out_train.asnumpy() == 0).sum() > 10  # some dropped


def _dropout_as_before(x, key, p, axes):
    """`ops.nn.dropout` as it read before the mask went behind a barrier
    (PR 31): the expression the new form has to equal bit for bit."""
    shape = tuple(n if not axes or i in axes else 1
                  for i, n in enumerate(x.shape))
    keep = 1.0 - p
    mask = jax.random.bernoulli(nn_ops._dropout_key(key), keep, shape)
    return jnp.where(mask, x / keep, 0).astype(x.dtype)


def _under(transform):
    """fn(x, g, key) -> fn run under one of the transforms a dropout site
    meets: a jitted step, a vmapped cell, a rematerialised block, a batch
    sharded over four devices."""
    if transform == "jit":
        return jax.jit
    if transform == "vmap":
        def batched(fn):
            def run(x, g, key):
                return jax.vmap(fn)(x, g, jax.random.split(key, x.shape[0]))
            return run
        return batched
    if transform == "checkpoint":
        return lambda fn: jax.jit(jax.checkpoint(fn))
    mesh = Mesh(onp.asarray(jax.devices()[:4]), ("dp",))
    rows, rep = NamedSharding(mesh, P("dp")), NamedSharding(mesh, P())
    return lambda fn: jax.jit(fn, in_shardings=(rows, rows, rep),
                              out_shardings=(rows, rows))


@pytest.mark.parametrize("transform", ["jit", "vmap", "checkpoint", "dp4"])
@pytest.mark.parametrize("axes", [None, (0, 2)], ids=["full", "axes02"])
@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_dropout_mask_bits_unchanged(dtype, axes, transform):
    """The keep-mask is made once, behind `lax.optimization_barrier`, so
    that a compiled step writes it once (tests/test_tpu_compile.py); the
    draw, the compare and the `where` are the old ones, so value and
    gradient are the old bits under every transform."""
    key = jax.random.key(31)
    kx, kg, kd = jax.random.split(key, 3)
    x = jax.random.normal(kx, (8, 16, 128), jnp.float32).astype(dtype)
    g = jax.random.normal(kg, x.shape, jnp.float32).astype(dtype)

    def value_and_grad(drop):
        def fn(x, g, key):
            out, pull = jax.vjp(lambda v: drop(v, key, 0.1, axes), x)
            return out, pull(g)[0]
        return fn

    new = _under(transform)(value_and_grad(nn_ops.dropout))(x, g, kd)
    old = _under(transform)(value_and_grad(_dropout_as_before))(x, g, kd)
    for a, b in zip(new, old):
        assert a.dtype == b.dtype == x.dtype and a.shape == x.shape
        assert onp.asarray(a).tobytes() == onp.asarray(b).tobytes()
    dropped = float((onp.asarray(new[0], "float32") == 0).mean())
    assert 0.02 < dropped < 0.25


@pytest.mark.parametrize("p, mode", [(0.0, "training"), (0.5, "eval")])
def test_dropout_off_returns_its_input(p, mode):
    x = jnp.ones((4, 8))
    assert nn_ops.dropout(x, jax.random.key(0), p=p, mode=mode) is x


def test_conv2d():
    conv = nn.Conv2D(4, kernel_size=3, padding=1)
    conv.initialize()
    x = mx.np.random.normal(0, 1, (2, 3, 8, 8))
    out = conv(x)
    assert out.shape == (2, 4, 8, 8)
    assert conv.weight.shape == (4, 3, 3, 3)
    # stride
    conv2 = nn.Conv2D(4, kernel_size=3, strides=2, padding=1)
    conv2.initialize()
    assert conv2(x).shape == (2, 4, 4, 4)


def test_conv_matches_numpy():
    conv = nn.Conv2D(1, kernel_size=2, use_bias=False, in_channels=1)
    conv.initialize(mx.init.One())
    x = mx.np.arange(16).reshape(1, 1, 4, 4)
    out = conv(x).asnumpy()
    xa = x.asnumpy()[0, 0]
    expect = onp.array([[xa[i:i+2, j:j+2].sum() for j in range(3)]
                        for i in range(3)])
    assert_almost_equal(out[0, 0], expect)


def test_conv_transpose():
    deconv = nn.Conv2DTranspose(3, kernel_size=2, strides=2)
    deconv.initialize()
    x = mx.np.random.normal(0, 1, (2, 5, 4, 4))
    assert deconv(x).shape == (2, 3, 8, 8)


def test_pooling():
    x = mx.np.arange(16).reshape(1, 1, 4, 4)
    assert nn.MaxPool2D(2)(x).asnumpy()[0, 0].tolist() == [[5, 7], [13, 15]]
    avg = nn.AvgPool2D(2)(x).asnumpy()[0, 0]
    assert_almost_equal(avg, onp.array([[2.5, 4.5], [10.5, 12.5]]))
    assert nn.GlobalAvgPool2D()(x).shape == (1, 1, 1, 1)
    assert nn.GlobalMaxPool2D()(x).asnumpy().item() == 15


def test_embedding():
    emb = nn.Embedding(10, 4)
    emb.initialize()
    idx = mx.np.array([1, 3, 5], dtype="int32")
    assert emb(idx).shape == (3, 4)


def test_layernorm_groupnorm():
    ln = nn.LayerNorm(in_channels=6)
    ln.initialize()
    x = mx.np.random.normal(3, 2, (4, 6))
    out = ln(x).asnumpy()
    assert_almost_equal(out.mean(axis=-1), onp.zeros(4), atol=1e-5)
    assert_almost_equal(out.std(axis=-1), onp.ones(4), rtol=1e-2, atol=1e-2)

    gn = nn.GroupNorm(num_groups=2, in_channels=4)
    gn.initialize()
    assert gn(mx.np.random.normal(0, 1, (2, 4, 3))).shape == (2, 4, 3)


def test_activations():
    x = mx.np.array([-1.0, 0.0, 1.0])
    assert nn.Activation("relu")(x).asnumpy().tolist() == [0, 0, 1]
    for layer in [nn.LeakyReLU(0.1), nn.ELU(), nn.SELU(), nn.GELU(),
                  nn.Swish(), nn.PReLU()]:
        layer.initialize()
        out = layer(x)
        assert out.shape == (3,)


def test_sequential_indexing():
    net = nn.HybridSequential()
    net.add(nn.Dense(3), nn.Dense(2), nn.Dense(1))
    assert len(net) == 3
    assert isinstance(net[1], nn.Dense)


def test_trainer_sgd_momentum():
    net = nn.Dense(1, in_units=1, use_bias=False)
    net.initialize(mx.init.One())
    trainer = Trainer(net.collect_params(), "sgd",
                      {"learning_rate": 0.1, "momentum": 0.9})
    x = mx.np.array([[1.0]])
    with autograd.record():
        y = net(x).sum()
    y.backward()
    trainer.step(1)
    # w = 1 - 0.1*1 = 0.9
    assert_almost_equal(net.weight.data(), onp.array([[0.9]]))


def test_trainer_states_roundtrip(tmp_path):
    net = nn.Dense(2, in_units=2)
    net.initialize()
    trainer = Trainer(net.collect_params(), "adam", {"learning_rate": 0.01})
    x = mx.np.ones((1, 2))
    with autograd.record():
        loss = net(x).sum()
    loss.backward()
    trainer.step(1)
    fname = str(tmp_path / "trainer.states")
    trainer.save_states(fname)
    trainer.load_states(fname)


def test_zero_grad_block():
    net = nn.Dense(2, in_units=2)
    net.initialize()
    with autograd.record():
        loss = net(mx.np.ones((1, 2))).sum()
    loss.backward()
    net.zero_grad()
    assert net.weight.grad().asnumpy().sum() == 0


def test_cast():
    net = nn.Dense(2, in_units=3)
    net.initialize()
    net.cast("float16")
    assert net.weight.data().dtype == onp.float16


def test_forward_hooks():
    net = nn.Dense(2, in_units=2)
    net.initialize()
    calls = []
    h1 = net.register_forward_pre_hook(lambda blk, args: calls.append("pre"))
    h2 = net.register_forward_hook(lambda blk, args, out: calls.append("post"))
    net(mx.np.ones((1, 2)))
    assert calls == ["pre", "post"]
    h1.detach()
    h2.detach()
    net(mx.np.ones((1, 2)))
    assert calls == ["pre", "post"]


def test_mlp_training_convergence():
    """End-to-end sanity: tiny MLP fits a linear function (reference:
    tests/python/train/test_autograd.py pattern)."""
    onp.random.seed(0)
    w_true = onp.array([[2.0], [-3.0]])
    x_np = onp.random.normal(0, 1, (64, 2)).astype(onp.float32)
    y_np = x_np @ w_true
    x, y = mx.np.array(x_np), mx.np.array(y_np)
    net = nn.Dense(1, in_units=2)
    net.initialize()
    net.hybridize()
    l2 = gloss.L2Loss()
    trainer = Trainer(net.collect_params(), "sgd", {"learning_rate": 0.5})
    for _ in range(50):
        with autograd.record():
            loss = l2(net(x), y)
        loss.backward()
        trainer.step(64)
    assert float(loss.mean()) < 1e-3
    assert_almost_equal(net.weight.data(), w_true.T, rtol=1e-2, atol=1e-2)
