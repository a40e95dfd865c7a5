"""Device-side augmentation: the fused-step prologue for image input.

The compact-bytes contract: pixels cross the host->device wire exactly
once, as uint8 NHWC, and EVERYTHING
float-valued happens on the chip where XLA fuses it into the first conv
— normalization, the NCHW transpose, and (new) train-time random
crop/flip.  The host ships the pre-crop canvas (e.g. 256x256) and the
device crops to the train size, trading ~(canvas/crop)^2 extra uint8
wire bytes for zero host float traffic and a bit-deterministic augment
stream.

Randomness pulls from the stateless threefry stream (``random.new_key``)
exactly like ``npx.dropout``: inside a hybridized/fused forward the key
comes from the traced key-stream scope, so the augment is part of the
single donated XLA program and replays deterministically per
(seed, step).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as onp

from ... import random as _rng
from ...ops.invoke import invoke, is_training
from ..block import HybridBlock

__all__ = ["DeviceAugment"]


def _augment_math(x, key, ch, cw, rand_crop, rand_mirror, mean, std,
                  scale, to_nchw, out_dtype):
    """Pure jnp math: NHWC uint8 canvas -> augmented/normalized batch.
    ``key=None`` means eval mode (center crop, no flip)."""
    B, H, W, C = x.shape
    if key is not None:
        ky, kx, kf = jax.random.split(key, 3)
    if (H, W) != (ch, cw):
        if key is not None and rand_crop:
            y0 = jax.random.randint(ky, (B,), 0, H - ch + 1)
            x0 = jax.random.randint(kx, (B,), 0, W - cw + 1)
            x = jax.vmap(lambda im, y, xx: jax.lax.dynamic_slice(
                im, (y, xx, 0), (ch, cw, C)))(x, y0, x0)
        else:
            y0, x0 = (H - ch) // 2, (W - cw) // 2
            x = x[:, y0:y0 + ch, x0:x0 + cw, :]
    if key is not None and rand_mirror:
        flip = jax.random.bernoulli(kf, 0.5, (B,))
        x = jnp.where(flip[:, None, None, None], x[:, :, ::-1, :], x)
    # float math strictly AFTER the geometric ops: crop/flip on uint8
    # keeps the fused program's working set at 1/4 the f32 size
    x = x.astype(out_dtype)
    if scale != 1.0:
        x = x * scale
    if mean is not None:
        x = x - mean
    if std is not None:
        x = x / std
    if to_nchw:
        x = jnp.transpose(x, (0, 3, 1, 2))
    return x


class DeviceAugment(HybridBlock):
    """Crop/flip/normalize/transpose on device, from uint8 NHWC batches.

    Drop it in front of a model (or call it in the train step) fed by
    ``ImageRecordIter(rand_crop=False, rand_mirror=False)`` host canvases:

    >>> aug = DeviceAugment((224, 224), rand_crop=True, rand_mirror=True,
    ...                     mean=(123.68, 116.28, 103.53),
    ...                     std=(58.4, 57.12, 57.38))
    >>> y = net(aug(x_uint8_nhwc))

    In train mode (``autograd.train_mode`` / the fused step) crops are
    random and flips coin-flip per image off the threefry stream; in
    eval it center-crops deterministically.  ``layout='NCHW'`` (default)
    emits the reference layout; pass ``'NHWC'`` to skip the transpose.
    ``mean``/``std`` are per-channel RGB in 0-255 units (set
    ``scale=1/255`` first if the model expects 0-1 inputs).
    """

    def __init__(self, size=None, rand_crop=False, rand_mirror=False,
                 mean=None, std=None, scale=1.0, layout="NCHW",
                 dtype="float32"):
        super().__init__()
        if size is not None and not isinstance(size, (tuple, list)):
            size = (size, size)
        self._size = tuple(size) if size is not None else None
        self._rand_crop = bool(rand_crop)
        self._rand_mirror = bool(rand_mirror)
        self._scale = float(scale)
        if layout not in ("NCHW", "NHWC"):
            raise ValueError("layout must be NCHW or NHWC")
        self._layout = layout
        self._dtype = jnp.dtype(dtype).type
        # channel vectors broadcast against NHWC's trailing axis
        self._mean = None if mean is None else \
            jnp.asarray(onp.asarray(mean, onp.float32)).astype(self._dtype)
        self._std = None if std is None else \
            jnp.asarray(onp.asarray(std, onp.float32)).astype(self._dtype)

    def forward(self, x):
        if x.ndim != 4:
            raise ValueError("DeviceAugment expects NHWC batches")
        ch, cw = self._size if self._size is not None else x.shape[1:3]
        if x.shape[1] < ch or x.shape[2] < cw:
            raise ValueError(
                f"canvas {x.shape[1:3]} smaller than crop {(ch, cw)}")
        augment = is_training() and (self._rand_crop or self._rand_mirror)
        key = _rng.new_key() if augment else None
        return invoke(
            lambda d: _augment_math(
                d, key, ch, cw, self._rand_crop, self._rand_mirror,
                self._mean, self._std, self._scale, self._layout == "NCHW",
                self._dtype),
            (x,), name="device_augment", differentiable=False)
