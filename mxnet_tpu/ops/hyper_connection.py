"""mHC's two passes over a sublayer's residual streams as Mosaic kernels.

For the streams X (N tokens, n streams of U; `models.decoder.HyperConnection`)
a sublayer F is wrapped as

    z  = (vec X) W * rsqrt(mean(vec X ^ 2) + eps)      (N, 2n + n^2), f32
    H_pre = sigmoid(alpha_0 z_pre + b_pre)              (N, n)
    u  = sum_j H_pre[j] X_j                             pre-mix, (N, U)
    X' = H_res X + H_post^T F(u)                        combine, (N, n, U)

with H_post and H_res (Sinkhorn) made from z by the caller, in XLA.  Both
passes read every stream of a token, so each is a kernel whose grid step is
`_TOKENS_AT_ONCE` tokens with their whole flat row (n U) in VMEM: the streams
read once, everything between the loads and the stores in f32, the outputs
stored in the streams' dtype.  Four kernels under two `jax.custom_vjp`s:

- `mhc_pre_fwd`: the row's sum of squares, z (the projection on the MXU, bf16
  operands and f32 sums where the streams are bf16), H_pre and u.  OUT: u and
  z.  `mixes` then makes H_post and H_res from z in XLA, differentiated by
  autodiff (`jax.vjp`, its residuals kept).
- `mhc_pre_bwd`: given X, z, du, the cotangent of z from H_post and H_res,
  and the cotangent of the streams the combine saw (``dxc``) with H_res: dH_pre
  from du and X in the tile, so the whole dz; then dX = H_pre du + the
  projection's and the norm's terms + H_res^T dxc, and W's gradient summed over
  the token tiles (a sequential grid).
- `mhc_post_fwd`: X' from X, F(u), H_post and H_res.
- `mhc_post_bwd`: dF(u) = sum_i H_post[i] dX'_i, dH_post and dH_res, each a
  sum over U in the tile.

**The fold.**  The streams reach the combine through `mixes`' fourth output,
which is X itself.  `combine(..., folded=True)` gives that output dX' as its
cotangent, unmixed, and `mhc_pre_bwd` applies H_res^T to it in the pass in
which it writes dX: the combine's backward writes no dX of its own, one
stream tensor written and read less a sublayer.  The pair is exact only
together, `mixes`' fourth output consumed by one folded combine alone (which is
how `HyperConnection.around` uses them); `combine(..., folded=False)`, as
`models.decoder._hc_combine` takes it, returns H_res^T dX' like any function.

Matmuls in f32 where one operand is f32 and the other bf16 (the cotangent of z
against W or X) go as three bf16 passes over the f32 operand's parts (its 24
bits of mantissa): the f32 product, as XLA's HIGHEST gives it, in half of
that precision's passes.  Tokens that are no multiple of the tile are padded
with zero rows, which give zero gradients.

`takes_kernels` chooses them from platform and shapes alone: on TPU, U a
multiple of 128 lanes; the XLA form (`models.decoder`) everywhere else, and
as the tests' reference.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import context as _context
from .pallas_kernels import _prec

__all__ = ["takes_kernels", "mixes", "combine"]

_F32, _BF16 = jnp.float32, jnp.bfloat16
_TOKENS_AT_ONCE = 128   # tokens a grid step: 3.7 MB of bf16 streams at n U = 14,336
_LANES_AT_ONCE = 512    # columns of one stream a step of a kernel's inner loop
# the backward kernel's blocks, double-buffered, pass Mosaic's default 16 MiB
# of scoped VMEM (a v5e core has 128 MiB)
_VMEM_LIMIT = 100 * 1024 * 1024


def takes_kernels(units):
    """The streams' passes in kernels: on TPU, a stream a multiple of 128
    lanes wide."""
    return _context.on_tpu() and units % 128 == 0


def _chunks(units):
    """Static column slices covering one stream of ``units``."""
    width = next(c for c in (_LANES_AT_ONCE, 384, 256, 128) if units % c == 0)
    return [slice(c, c + width) for c in range(0, units, width)]


def _lanes(j, units, cols):
    """Stream j's columns ``cols`` in the flat row."""
    return slice(j * units + cols.start, j * units + cols.stop)


def _col(v, k):
    """Column k of a (rows, width) f32 value, (rows, 1)."""
    onehot = jax.lax.broadcasted_iota(jnp.int32, (1, v.shape[1]), 1) == k
    return jnp.sum(jnp.where(onehot, v, 0.0), axis=1, keepdims=True)


def _cols(columns, width):
    """(rows, width) f32 with ``columns`` (each (rows, 1)) first, zeros after."""
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, width), 1)
    return sum(jnp.where(lane == k, c, 0.0) for k, c in enumerate(columns))


def _dot(a, b, dims):
    """The f32 product of a and b: native where both are bf16 (exact products,
    f32 sums) or both f32 (HIGHEST); an f32 ``a`` against a bf16 ``b`` as the
    sum of three bf16 parts of ``a``, which hold all of its mantissa."""
    dot = functools.partial(jax.lax.dot_general, dimension_numbers=dims,
                            preferred_element_type=_F32)
    if a.dtype == b.dtype:
        return dot(a, b, precision=_prec(a.dtype))
    parts, rest = [], a
    for _ in range(3):
        parts.append(rest.astype(_BF16))
        rest = rest - parts[-1].astype(_F32)
    return sum(dot(p, b, precision=_prec(_BF16)) for p in reversed(parts))


_NT = (((1,), (1,)), ((), ()))    # a (m, k) . b (n, k)^T
_NN = (((1,), (0,)), ((), ()))
_TN = (((0,), (0,)), ((), ()))    # a (k, m)^T . b (k, n)


def _pre_of(z, ab, n):
    """H_pre from z (rows, 2n + n^2) and ab = [alpha_0; b_pre] (2, n)."""
    return jax.nn.sigmoid(ab[0:1, :] * z[:, :n] + ab[1:2, :])


def _pre_fwd_kernel(x, wt, ab, u, z_out, *, n, eps):
    units = u.shape[1]
    squares, zraw = 0.0, 0.0
    for j in range(n):
        for cols in _chunks(units):
            xs = x[:, _lanes(j, units, cols)]
            xf = xs.astype(_F32)
            squares = squares + xf * xf
            zraw = zraw + _dot(xs, wt[:, _lanes(j, units, cols)], _NT)
    scale = jax.lax.rsqrt(jnp.sum(squares, axis=1, keepdims=True)
                          / (n * units) + eps)
    z = zraw * scale
    z_out[...] = z
    pre = _pre_of(z, ab[...], n)
    weights = [_col(pre, j) for j in range(n)]
    for cols in _chunks(units):
        u[:, cols] = sum(w * x[:, _lanes(j, units, cols)].astype(_F32)
                         for j, w in enumerate(weights)).astype(u.dtype)


def _pre_bwd_kernel(x, wt, ab, z_in, dz_in, du, dxc, res, dx, dlogit, dwt, *,
                    n, eps):
    @pl.when(pl.program_id(0) == 0)
    def _():
        dwt[...] = jnp.zeros_like(dwt)

    units = du.shape[1]
    squares, dpre = 0.0, [0.0] * n
    for cols in _chunks(units):
        duf = du[:, cols].astype(_F32)
        for j in range(n):
            xf = x[:, _lanes(j, units, cols)].astype(_F32)
            squares = squares + xf * xf
            dpre[j] = dpre[j] + duf * xf
    scale = jax.lax.rsqrt(jnp.sum(squares, axis=1, keepdims=True)
                          / (n * units) + eps)
    z, abv = z_in[...], ab[...]
    pre = _pre_of(z, abv, n)
    dpre = _cols([jnp.sum(d, axis=1, keepdims=True) for d in dpre], n)
    dl = dpre * pre * (1.0 - pre)
    dlogit[...] = dl
    dz = dz_in[...] + _cols([_col(abv[0:1, :] * dl, j) for j in range(n)],
                            z.shape[1])
    # the norm's row term: d/dx rsqrt(mean(x^2) + eps) through z = zraw scale
    norm = -jnp.sum(dz * z, axis=1, keepdims=True) * scale * scale / (n * units)
    dzraw = dz * scale
    weights = [_col(pre, j) for j in range(n)]
    resv = res[...]
    mix = [[_col(resv, i * n + j) for i in range(n)] for j in range(n)]
    for cols in _chunks(units):
        duf = du[:, cols].astype(_F32)
        dxcs = [dxc[:, _lanes(i, units, cols)].astype(_F32) for i in range(n)]
        for j in range(n):
            lanes = _lanes(j, units, cols)
            xs = x[:, lanes]
            g = weights[j] * duf + norm * xs.astype(_F32) \
                + _dot(dzraw, wt[:, lanes], _NN) \
                + sum(m * d for m, d in zip(mix[j], dxcs))
            dx[:, lanes] = g.astype(dx.dtype)
            dwt[:, lanes] += _dot(dzraw, xs, _TN)


def _post_fwd_kernel(x, y, post, res, out, *, n):
    units = y.shape[1]
    postv, resv = post[...], res[...]
    posts = [_col(postv, i) for i in range(n)]
    mix = [[_col(resv, i * n + j) for j in range(n)] for i in range(n)]
    for cols in _chunks(units):
        yf = y[:, cols].astype(_F32)
        xs = [x[:, _lanes(j, units, cols)].astype(_F32) for j in range(n)]
        for i in range(n):
            out[:, _lanes(i, units, cols)] = (
                sum(m * xj for m, xj in zip(mix[i], xs)) + posts[i] * yf
            ).astype(out.dtype)


def _post_bwd_kernel(x, y, post, dout, dy, dpost, dres, *, n):
    units = y.shape[1]
    postv = post[...]
    posts = [_col(postv, i) for i in range(n)]
    dp, dr = [0.0] * n, [0.0] * (n * n)
    for cols in _chunks(units):
        yf = y[:, cols].astype(_F32)
        ds = [dout[:, _lanes(i, units, cols)].astype(_F32) for i in range(n)]
        dy[:, cols] = sum(p * d for p, d in zip(posts, ds)).astype(dy.dtype)
        for i in range(n):
            dp[i] = dp[i] + jnp.sum(ds[i] * yf, axis=1, keepdims=True)
        for j in range(n):
            xf = x[:, _lanes(j, units, cols)].astype(_F32)
            for i in range(n):
                dr[i * n + j] = dr[i * n + j] + jnp.sum(ds[i] * xf, axis=1,
                                                        keepdims=True)
    dpost[...] = _cols(dp, n)
    dres[...] = _cols(dr, n * n)


def _rows(tile, width):
    return pl.BlockSpec((tile, width), lambda t: (t, 0))


def _whole(shape):
    return pl.BlockSpec(shape, lambda t: (0,) * len(shape))


def _params(semantics):
    return pltpu.CompilerParams(dimension_semantics=(semantics,),
                                vmem_limit_bytes=_VMEM_LIMIT)


def _tile(tokens):
    return min(_TOKENS_AT_ONCE, tokens)


@functools.partial(jax.jit, static_argnames=("n", "eps", "interpret"))
def _mhc_pre_fwd(x, wt, ab, n, eps, interpret):
    (tokens, flat), width = x.shape, wt.shape[0]
    tile = _tile(tokens)
    return pl.pallas_call(
        functools.partial(_pre_fwd_kernel, n=n, eps=eps),
        grid=(tokens // tile,),
        in_specs=[_rows(tile, flat), _whole(wt.shape), _whole(ab.shape)],
        out_specs=[_rows(tile, flat // n), _rows(tile, width)],
        out_shape=[jax.ShapeDtypeStruct((tokens, flat // n), x.dtype),
                   jax.ShapeDtypeStruct((tokens, width), _F32)],
        compiler_params=_params("parallel"), interpret=interpret,
        name="mhc_pre_fwd",
    )(x, wt, ab)


@functools.partial(jax.jit, static_argnames=("n", "eps", "interpret"))
def _mhc_pre_bwd(x, wt, ab, z, dz, du, dxc, res, n, eps, interpret):
    (tokens, flat), width = x.shape, wt.shape[0]
    tile = _tile(tokens)
    return pl.pallas_call(
        functools.partial(_pre_bwd_kernel, n=n, eps=eps),
        grid=(tokens // tile,),
        in_specs=[_rows(tile, flat), _whole(wt.shape), _whole(ab.shape),
                  _rows(tile, width), _rows(tile, width),
                  _rows(tile, flat // n), _rows(tile, flat),
                  _rows(tile, n * n)],
        out_specs=[_rows(tile, flat), _rows(tile, n), _whole(wt.shape)],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct((tokens, n), _F32),
                   jax.ShapeDtypeStruct(wt.shape, _F32)],
        # W's gradient is summed over the token tiles in one block
        compiler_params=_params("arbitrary"), interpret=interpret,
        name="mhc_pre_bwd",
    )(x, wt, ab, z, dz, du, dxc, res)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _mhc_post_fwd(x, y, post, res, interpret):
    (tokens, flat), n = x.shape, post.shape[1]
    tile = _tile(tokens)
    return pl.pallas_call(
        functools.partial(_post_fwd_kernel, n=n),
        grid=(tokens // tile,),
        in_specs=[_rows(tile, flat), _rows(tile, flat // n), _rows(tile, n),
                  _rows(tile, n * n)],
        out_specs=_rows(tile, flat),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        compiler_params=_params("parallel"), interpret=interpret,
        name="mhc_post_fwd",
    )(x, y, post, res)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _mhc_post_bwd(x, y, post, dout, interpret):
    (tokens, flat), n = x.shape, post.shape[1]
    tile = _tile(tokens)
    return pl.pallas_call(
        functools.partial(_post_bwd_kernel, n=n),
        grid=(tokens // tile,),
        in_specs=[_rows(tile, flat), _rows(tile, flat // n), _rows(tile, n),
                  _rows(tile, flat)],
        out_specs=[_rows(tile, flat // n), _rows(tile, n), _rows(tile, n * n)],
        out_shape=[jax.ShapeDtypeStruct(y.shape, y.dtype),
                   jax.ShapeDtypeStruct((tokens, n), _F32),
                   jax.ShapeDtypeStruct((tokens, n * n), _F32)],
        compiler_params=_params("parallel"), interpret=interpret,
        name="mhc_post_bwd",
    )(x, y, post, dout)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _pre(x, wt, alpha, bias, n, post_and_res, eps, interpret):
    """(u, H_post, H_res, x) of the flat streams x (tokens, n U) with wt (2n +
    n^2, n U) the projection transposed; ``post_and_res``(z, alpha, bias)
    makes H_post and H_res from the logits in XLA."""
    return _pre_fwd(x, wt, alpha, bias, n, post_and_res, eps, interpret)[0]


def _pre_fwd(x, wt, alpha, bias, n, post_and_res, eps, interpret):
    ab = jnp.stack([jnp.broadcast_to(alpha[0], (n,)), bias[:n]]).astype(_F32)
    u, z = _mhc_pre_fwd(x, wt, ab, n, eps, interpret)
    (post, res), vjp = jax.vjp(post_and_res, z, alpha, bias)
    return (u, post, res, x), (x, wt, ab, z, res, vjp)


def _pre_bwd(n, post_and_res, eps, interpret, saved, cts):
    x, wt, ab, z, res, vjp = saved
    du, dpost, dres, dxc = cts
    dz, dalpha, dbias = vjp((dpost, dres))
    dx, dl, dwt = _mhc_pre_bwd(x, wt, ab, z, dz, du, dxc,
                               res.reshape(res.shape[0], n * n), n, eps,
                               interpret)
    dalpha = dalpha.at[0].add(jnp.sum(dl * z[:, :n]))
    dbias = dbias.at[:n].add(jnp.sum(dl, axis=0))
    return dx, dwt.astype(wt.dtype), dalpha, dbias


_pre.defvjp(_pre_fwd, _pre_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _combine(x, y, post, res, folded, interpret):
    """H_res x + H_post^T y of the flat streams x (tokens, n U), post (tokens,
    n) and res (tokens, n^2)."""
    return _mhc_post_fwd(x, y, post, res, interpret)


def _combine_fwd(x, y, post, res, folded, interpret):
    return _mhc_post_fwd(x, y, post, res, interpret), (x, y, post, res)


def _combine_bwd(folded, interpret, saved, dout):
    x, y, post, res = saved
    n = post.shape[1]
    dy, dpost, dres = _mhc_post_bwd(x, y, post, dout, interpret)
    if folded:      # H_res^T is applied by `mhc_pre_bwd`
        return dout, dy, dpost, dres
    dx = jnp.einsum("tij,tiu->tju", res.reshape(-1, n, n),
                    dout.reshape(dout.shape[0], n, -1).astype(_F32))
    return dx.reshape(x.shape).astype(x.dtype), dy, dpost, dres


_combine.defvjp(_combine_fwd, _combine_bwd)


def _padded(a, tokens):
    """a (tokens, ...) with zero rows up to a whole number of tiles."""
    tile = min(_TOKENS_AT_ONCE, -(-tokens // 16) * 16)
    pad = -tokens % tile
    return jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1)) if pad else a


def mixes(x, w, alpha, bias, post_and_res, eps):
    """The pre-mix of the streams x (B, T, n, U) through `mhc_pre_fwd`: (u
    (B, T, U) in x's dtype, H_post (B, T, n), H_res (B, T, n, n), the streams
    for a ``folded`` `combine`).  w (n U, 2n + n^2) is the projection with the
    norm's gain in it, alpha (3,) and bias (2n + n^2,) the mixes' scales and
    biases; ``post_and_res``(z, alpha, bias) makes H_post and H_res from the
    logits z (..., 2n + n^2), f32."""
    b, t, n, units = x.shape
    tokens = b * t
    u, post, res, streams = _pre(
        _padded(x.reshape(tokens, n * units), tokens), w.T, alpha, bias, n,
        post_and_res, eps, not _context.on_tpu())
    return (u[:tokens].reshape(b, t, units), post[:tokens].reshape(b, t, n),
            res[:tokens].reshape(b, t, n, n),
            streams[:tokens].reshape(x.shape))


def combine(x, y, post, res, folded=False):
    """H_res X + H_post^T y through `mhc_post_fwd`: x (B, T, n, U), y (B, T,
    U), post (B, T, n), res (B, T, n, n); X' in x's dtype.  ``folded``: x is
    `mixes`' fourth output and this call its one consumer (the fold, above)."""
    b, t, n, units = x.shape
    tokens = b * t
    out = _combine(*(_padded(a.reshape(tokens, -1), tokens)
                     for a in (x, y, post, res)),
                   folded, not _context.on_tpu())
    return out[:tokens].reshape(x.shape)
