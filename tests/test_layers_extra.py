"""PixelShuffle / DeformableConvolution / callback / model-checkpoint tests."""
import logging

import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd
from mxnet_tpu.gluon import nn


def test_pixel_shuffle_1d2d3d():
    x1 = mx.np.array(onp.arange(2 * 6 * 4, dtype="float32").reshape(2, 6, 4))
    out1 = nn.PixelShuffle1D(3)(x1)
    assert out1.shape == (2, 2, 12)

    x2 = mx.np.array(onp.arange(1 * 8 * 2 * 3, dtype="float32")
                     .reshape(1, 8, 2, 3))
    out2 = nn.PixelShuffle2D(2)(x2)
    assert out2.shape == (1, 2, 4, 6)
    # depth-to-space correctness: channel c*4+fy*2+fx lands at (y*2+fy, x*2+fx)
    src = x2.asnumpy()
    got = out2.asnumpy()
    assert got[0, 0, 1, 0] == src[0, 2, 0, 0]  # fy=1, fx=0 -> channel 2
    assert got[0, 1, 0, 1] == src[0, 5, 0, 0]  # c=1, fx=1 -> channel 5

    x3 = mx.np.ones((1, 8, 2, 2, 2))
    assert nn.PixelShuffle3D(2)(x3).shape == (1, 1, 4, 4, 4)


def test_deformable_conv_zero_offset_matches_conv():
    """With zero offsets (the default init), DeformableConvolution equals a
    regular convolution with the same weight (reference contract)."""
    onp.random.seed(0)
    x = mx.np.array(onp.random.rand(2, 3, 9, 9).astype("float32"))
    dcn = nn.DeformableConvolution(5, kernel_size=3, padding=1,
                                   in_channels=3)
    dcn.initialize()
    out = dcn(x)
    assert out.shape == (2, 5, 9, 9)

    conv = nn.Conv2D(5, 3, padding=1, in_channels=3)
    conv.initialize()
    conv.weight.set_data(dcn.weight.data())
    conv.bias.set_data(dcn.bias.data())
    ref = conv(x)
    assert onp.allclose(out.asnumpy(), ref.asnumpy(), atol=1e-4)


def test_deformable_conv_offsets_shift_sampling():
    # constant +1.0 y-offset on all taps = sampling one row down
    x = mx.np.array(onp.arange(25, dtype="float32").reshape(1, 1, 5, 5))
    dcn = nn.DeformableConvolution(1, kernel_size=1, padding=0,
                                   in_channels=1, use_bias=False)
    dcn.initialize()
    dcn.weight.set_data(mx.np.ones((1, 1, 1, 1)))
    base = dcn(x).asnumpy()
    dcn.offset.bias.set_data(mx.np.array([1.0, 0.0]))  # (dy, dx)
    shifted = dcn(x).asnumpy()
    assert onp.allclose(shifted[0, 0, :4], base[0, 0, 1:], atol=1e-4)


def test_deformable_conv_grad_flows():
    x = mx.np.array(onp.random.rand(1, 2, 6, 6).astype("float32"))
    dcn = nn.DeformableConvolution(3, kernel_size=3, padding=1,
                                   in_channels=2)
    dcn.initialize()
    with autograd.record():
        loss = dcn(x).sum()
    loss.backward()
    g = dcn.offset.weight.grad()
    assert g is not None and g.shape[0] == 18


def test_speedometer_and_log_metric(caplog):
    from collections import namedtuple
    from mxnet_tpu.gluon.metric import Accuracy

    Param = namedtuple("Param", ["epoch", "nbatch", "eval_metric"])
    metric = Accuracy()
    metric.update(mx.np.array([0, 1]), mx.np.array([[0.9, 0.1], [0.2, 0.8]]))
    sp = mx.callback.Speedometer(batch_size=4, frequent=2)
    with caplog.at_level(logging.INFO):
        for nb in range(1, 5):
            sp(Param(0, nb, metric))
    assert any("samples/sec" in r.message for r in caplog.records)

    cb = mx.callback.log_train_metric(1)
    metric.update(mx.np.array([0]), mx.np.array([[0.9, 0.1]]))
    with caplog.at_level(logging.INFO):
        cb(Param(0, 1, metric))
    assert any("Train-accuracy" in r.message for r in caplog.records)


def test_model_checkpoint_roundtrip(tmp_path):
    prefix = str(tmp_path / "ckpt")
    arg = {"fc_weight": mx.np.ones((3, 2)), "fc_bias": mx.np.zeros(3)}
    aux = {"bn_mean": mx.np.full((3,), 0.5)}
    mx.model.save_checkpoint(prefix, 7, symbol='{"nodes": []}',
                             arg_params=arg, aux_params=aux)
    sym, arg2, aux2 = mx.model.load_checkpoint(prefix, 7)
    assert sym == '{"nodes": []}'
    assert onp.allclose(arg2["fc_weight"].asnumpy(), 1.0)
    assert onp.allclose(aux2["bn_mean"].asnumpy(), 0.5)

    # do_checkpoint callback writes on the right epochs
    cb = mx.callback.do_checkpoint(prefix, period=2)
    cb(1, None, arg, aux)  # epoch index 1 -> saves epoch 2
    import os
    assert os.path.exists(prefix + "-0002.params")


def test_stem_tiling_bit_parity_fwd_bwd():
    """Every (tm, tn) stem tile choice is bit-identical fwd and bwd:
    K is never split, and the backward is tile-independent XLA dots."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops.stem import (fold_stem_kernel, space_to_depth2,
                                    stem_conv_pallas)

    rng = onp.random.RandomState(5)
    x = jnp.asarray(rng.randn(2, 3, 32, 32).astype(onp.float32))
    w7 = jnp.asarray(rng.randn(16, 3, 7, 7).astype(onp.float32))
    xs = space_to_depth2(x)
    wf = fold_stem_kernel(w7)

    def loss(**tile):
        def f(xs, wf):
            return stem_conv_pallas(xs, wf, **tile).astype(jnp.float32).sum()
        return jax.value_and_grad(f, argnums=(0, 1))(xs, wf)

    val_a, grads_a = loss()                     # STEM_TILE_DEFAULT
    val_b, grads_b = loss(tm=64, tn=8)          # a very different tiling
    assert onp.array_equal(onp.asarray(val_a), onp.asarray(val_b))
    for ga, gb in zip(grads_a, grads_b):
        assert onp.array_equal(onp.asarray(ga), onp.asarray(gb))
