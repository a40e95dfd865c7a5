"""Space-to-depth ResNet stem.

The classic 7x7/stride-2 stem is the census's worst roofline offender:
at 3 input channels the MXU contraction dim is 3*7*7 = 147 done as a
strided conv XLA cannot tile densely, so the layer sits far below its
speed-of-light floor.  The MLPerf-era fix (arxiv 1909.09756 practice)
is algebraic, not approximate: pack 2x2 spatial blocks into channels
(space-to-depth) ONCE in the input pipeline, and fold the 7x7/s2
kernel into a 4x4/stride-1 kernel over the packed (4*C_in)-channel
input.  Same math, but now the conv is a dense stride-1 contraction
over K = 4*C_in*16 = 192 that lowers to one fat matmul.

Derivation (why the zero pad leads): with the 7x7 kernel zero-padded
to 8x8 by ONE LEADING row/col (w8[:, :, 1:, 1:] = w7), output pixel i
of the stride-2 conv reads input row 2i + p - 3 = 2*(i + ph - 2) + sh
where p+1 = 2*ph + sh — i.e. every tap lands on a packed pixel
(i + ph - 2, phase sh).  So the folded 4x4 kernel is

    wf[o, (sh*2 + sw)*C_in + c, ph, qw] = w8[o, c, 2*ph + sh, 2*qw + sw]

(the (sh, sw, c) channel order is exactly `legacy_math.space_to_depth`
packing) and the stride-1 conv needs asymmetric padding (2, 1) per
spatial dim.  The fold is a weight reshape — checkpoints keep the
original (C, C_in, 7, 7) layout and gradients flow through it.

Bias-free by design: the stem feeds a BatchNorm, which absorbs any
bias; a broadcast bias add would double the stem's output bytes and
dilute its census intensity below the floor this kernel exists to
clear.

Two lowerings:
* :func:`stem_conv` — pure XLA conv over the packed input.  What the
  census profiles (interpret-mode Pallas in a lowered HLO would hide
  the real cost model) and the CPU-mesh default.
* :func:`stem_conv_pallas` — the production TPU kernel: XLA-built
  im2col patches + one Pallas-tiled (M, 192) @ (192, C) matmul at
  tile sizes (tm, tn) = `STEM_TILE_DEFAULT`.  K is never split, so
  every tile choice is bit-identical (the tiling parity test rides
  this).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .. import context as _context
from .legacy_math import space_to_depth

__all__ = ["space_to_depth2", "fold_stem_kernel", "stem_conv",
           "s2d_stem_conv", "stem_conv_pallas", "reference_stem_conv",
           "stem_conv_auto", "STEM_TILE_DEFAULT"]

# The stem matmul's tile: targets for the (M, C_out) output block, fitted
# to the shape by `_fit_tile` (explicit `tm`/`tn` win).  Set on jax
# 0.4.37 on another machine; this machine has no number for it: no zoo
# model uses `SpaceToDepthStem`.  ROADMAP S9 decides the layer's fate —
# wired into `resnet50_v1`, the tile is re-picked from runs of
# `resnet50_v1.train` and this comment names the ledger line.
STEM_TILE_DEFAULT = {"tm": 512, "tn": 128}


def space_to_depth2(x):
    """Pack 2x2 spatial blocks into channels: (B, C, H, W) ->
    (B, 4C, H/2, W/2).  Belongs in the input pipeline (host side /
    root scope), NOT inside the stem layer."""
    return space_to_depth(x, 2)


def fold_stem_kernel(w7):
    """(C, C_in, 7, 7) stride-2 kernel -> (C, 4*C_in, 4, 4) stride-1
    kernel over the space-to-depth input (see module docstring)."""
    c_out, c_in, kh, kw = w7.shape
    if (kh, kw) != (7, 7):
        raise ValueError(f"stem fold expects a 7x7 kernel, got {kh}x{kw}")
    w8 = jnp.pad(w7, ((0, 0), (0, 0), (1, 0), (1, 0)))   # leading zeros
    w8 = w8.reshape(c_out, c_in, 4, 2, 4, 2)             # ph, sh, qw, sw
    wf = w8.transpose(0, 3, 5, 1, 2, 4)                  # (o, sh, sw, c, ph, qw)
    return wf.reshape(c_out, 4 * c_in, 4, 4)


def stem_conv(xs, wf):
    """XLA form: 4x4 stride-1 conv, asymmetric padding (2, 1), no bias.
    ``xs`` is the packed (B, 4*C_in, H/2, W/2) input."""
    return jax.lax.conv_general_dilated(
        xs, wf, window_strides=(1, 1), padding=((2, 1), (2, 1)),
        dimension_numbers=("NCHW", "OIHW", "NCHW"))


def s2d_stem_conv(x, w7):
    """Reference chain for tests: pack + fold + conv from the raw
    (B, C_in, H, W) input and the original 7x7 kernel."""
    return stem_conv(space_to_depth2(x), fold_stem_kernel(w7))


def reference_stem_conv(x, w7):
    """The original 7x7/stride-2/pad-3 stem conv (bias-free) the folded
    form must match exactly in structure (parity tests compare against
    this)."""
    return jax.lax.conv_general_dilated(
        x, w7, window_strides=(2, 2), padding=((3, 3), (3, 3)),
        dimension_numbers=("NCHW", "OIHW", "NCHW"))


# ---------------------------------------------------------------------------
# Pallas form
# ---------------------------------------------------------------------------
def _fit_tile(dim, target):
    """Largest power-of-two <= target dividing dim (>=8), else the whole
    dim as a single block — the same clamping rule as flash _pick_block,
    so cached tile targets stay legal for any concrete shape in the
    bucket."""
    b = 1
    while b * 2 <= min(target, dim):
        b *= 2
    while b >= 8:
        if dim % b == 0:
            return b
        b //= 2
    return dim


def _matmul_kernel(x_ref, w_ref, y_ref):
    from .pallas_kernels import _prec
    y_ref[...] = jnp.dot(
        x_ref[...], w_ref[...], precision=_prec(x_ref.dtype),
        preferred_element_type=jnp.float32).astype(y_ref.dtype)


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def _stem_matmul(patches, w2d, tm, tn, interpret):
    from jax.experimental import pallas as pl
    m, k = patches.shape
    _, n = w2d.shape
    grid = (m // tm, n // tn)
    return pl.pallas_call(
        _matmul_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((tm, k), lambda mi, ni: (mi, 0)),
                  pl.BlockSpec((k, tn), lambda mi, ni: (0, ni))],
        out_specs=pl.BlockSpec((tm, tn), lambda mi, ni: (mi, ni)),
        out_shape=jax.ShapeDtypeStruct((m, n), patches.dtype),
        interpret=interpret,
    )(patches, w2d)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def _stem_matmul_vjp(flat, w2d, tm, tn, interpret):
    return _stem_matmul(flat, w2d, tm, tn, interpret)


def _stem_matmul_fwd(flat, w2d, tm, tn, interpret):
    return _stem_matmul(flat, w2d, tm, tn, interpret), (flat, w2d)


def _stem_matmul_bwd(tm, tn, interpret, res, ct):
    # XLA dots: tile-choice-independent, so gradients are bitwise
    # identical across tilings for free
    flat, w2d = res
    ctf = ct.astype(jnp.float32)
    dflat = jnp.dot(ctf, w2d.astype(jnp.float32).T).astype(flat.dtype)
    dw2d = jnp.dot(flat.astype(jnp.float32).T, ctf).astype(w2d.dtype)
    return dflat, dw2d


_stem_matmul_vjp.defvjp(_stem_matmul_fwd, _stem_matmul_bwd)


def stem_conv_pallas(xs, wf, tm=None, tn=None, interpret=None):
    """Production TPU form of :func:`stem_conv`: im2col patches (XLA)
    feeding one Pallas-tiled matmul.  ``tm``/``tn`` default to
    `STEM_TILE_DEFAULT`.  K (= 4*C_in*16) is never split across tiles,
    so every (tm, tn) choice produces bit-identical results."""
    b, c_packed, h2, w2 = xs.shape
    c_out = wf.shape[0]
    tm = STEM_TILE_DEFAULT["tm"] if tm is None else tm
    tn = STEM_TILE_DEFAULT["tn"] if tn is None else tn
    # (B, C_patch, H2, W2) with C_patch ordered (channel, kh, kw) —
    # exactly wf's (4*C_in, 4, 4) flattening
    patches = jax.lax.conv_general_dilated_patches(
        xs, filter_shape=(4, 4), window_strides=(1, 1),
        padding=((2, 1), (2, 1)))
    k = patches.shape[1]
    m = b * h2 * w2
    flat = patches.transpose(0, 2, 3, 1).reshape(m, k)
    w2d = wf.reshape(c_out, k).T
    tm = _fit_tile(m, tm)
    tn = _fit_tile(c_out, tn)
    interp = (not _context.on_tpu()) if interpret is None else interpret
    out = _stem_matmul_vjp(flat, w2d, tm, tn, interp)
    return out.reshape(b, h2, w2, c_out).transpose(0, 3, 1, 2)


def stem_conv_auto(xs, w7):
    """The gluon ``SpaceToDepthStem`` forward: fold the canonical
    (C, C_in, 7, 7) weight and run the packed-input stem conv — the
    Pallas matmul form on a TPU backend, the pure-XLA conv elsewhere
    (what the census profiles; interpret-mode Pallas inside a lowered
    HLO would hide the real cost model) and in a program partitioned
    over a mesh (`parallel.mesh_scope`), which cannot split a Mosaic
    call.  Gradients flow through the fold to the 7x7 weight either way,
    so checkpoints keep the classic layout."""
    from ..parallel.mesh import current_mesh
    wf = fold_stem_kernel(w7)
    if _context.on_tpu() and current_mesh() is None:
        return stem_conv_pallas(xs, wf)
    return stem_conv(xs, wf)
