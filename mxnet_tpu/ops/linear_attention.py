"""Kimi Delta Attention's core as a chunked scan, and the short causal
convolution in front of it.

    kda(q, k, v, g, beta, chunk=64) -> o

q, k (B, T, H, K), v (B, T, H, V), g (B, T, H, K) the LOG decay of every key
channel (f32, <= 0), beta (B, T, H) the step size.  Per head, with the state
S (K, V) zero before the first token (Kimi Linear, arXiv:2510.26692, the
gated delta rule with a decay per channel):

    S_t = (I - beta_t k_t k_t^T) Diag(exp g_t) S_(t-1) + beta_t k_t v_t^T
    o_t = S_t^T q_t

The system never walks the tokens.  Over a chunk of C rows with the state
S_0 entering it, gamma_r = sum_(i<=r) g_i per channel, Gamma = exp(gamma):

    A[r, i] = sum_c k_r[c] k_i[c] exp(gamma_r[c] - gamma_i[c])     i <  r
    B[r, i] = sum_c q_r[c] k_i[c] exp(gamma_r[c] - gamma_i[c])     i <= r
    U   = (I + Diag(beta) A)^-1 Diag(beta) (V - (Gamma * K) S_0)
    O   = (Gamma * Q) S_0 + B U
    S_C = Diag(Gamma_C) S_0 + (exp(gamma_C - gamma) * K)^T U

Two phases, over `_CHUNKS_AT_ONCE` chunks at a time, group after group.
What does not need S_0 is computed for all chunks of a group at once: A and
B, the inverse X = (I + Diag(beta) A)^-1, and W = X Diag(beta) (Gamma * K),
U0 = X Diag(beta) V, so that U = U0 - W S_0.  Then a `lax.scan` over the
group's chunks carries S in f32 and does four matmuls an iteration, batched
over (B, H): W S, B U, (Gamma * Q) S and the state's update
(`_across_chunks`); an outer scan carries S from group to group.  The
backward pass keeps the state entering each GROUP and recomputes the group,
which keeps one state per chunk of that group (nothing per token, and never
T/C x K x V at once).

Decays.  A ratio exp(gamma_r - gamma_i) with i <= r is at most 1 and is
formed as such: never as Gamma_r * (1 / Gamma_i), whose second factor
overflows f32 after a few strongly decayed tokens.  Inside a chunk the rows
go in sub-blocks of 16.  Between sub-blocks I > J both factors are taken
against gamma at I's first row, exp(gamma_r - rho_I) * exp(rho_I - gamma_i),
each <= 1, so those blocks are matmuls; inside a sub-block the ratio is
formed per (r, i, channel) and summed.  A, B, the inverse and every decay
are f32; W, U0, B and the decayed Q and K go to the inputs' dtype for the
scan's matmuls, which accumulate in f32.

The first phase has two implementations of one mathematics, chosen from
platform and shapes alone (`_takes_kernels`: on TPU, K and V multiples of
128 lanes, the chunk a multiple of 16 rows; `mxtpu_linear_attention_lowerings
{path}` counts each trace under the one it took):

- "chunked_scan": `_within_chunks`, XLA ops over (chunks, B, H, C, .)
  tensors, q, k, v, g copied to chunk-major first; the inverse is
  `triangular_solve`.  Every platform but TPU, every other shape, and the
  tests' reference.  Each intermediate is an HBM pass (a dozen f32 tensors of
  268 MB a layer at the Kimi cell's shapes), and it is differentiated by
  autodiff.
- "pallas_chunk": `_within_chunks_kernels`, a `jax.custom_vjp` over two
  Mosaic kernels whose grid step is one chunk of one sequence and
  `_HEADS_AT_ONCE` heads.  IN: q, k, g (B, T, H K), v (B, T, H V) — the
  layer's own arrays, a head's (C, K) tile read at lane offset h K, so
  nothing is copied to chunk-major — and beta (B, T, H), whose block holds
  every head's column.  OUT of `kda_chunk_fwd`: the six values chunk-major,
  (chunks, B, H, C, .), what `_across_chunks` scans, in `_within_chunks`'s
  dtypes.  Everything between lives in VMEM (`_tiles`): gamma as a matmul
  with a triangle of ones, the sub-blocks' ratios a column at a time (the
  same `where` guards), the blocks between sub-blocks as matmuls, the
  inverse as the product of I + (-N)^(2^k) (N is nilpotent: five squarings
  of 64 x 64 in f32, two heads' side by side on the lanes; as XLA ops each
  squaring was an HBM pass and lost to `triangular_solve`, PERF.md section
  6, PR 34).  `kda_chunk_bwd` is given the inputs and the six cotangents
  alone (the `custom_vjp` keeps no other residual): it recomputes the tiles
  in VMEM and runs `jax.vjp` of the same `_tiles`, traced inside the
  kernel, writing dq, dk, dv, dg as (B, T, H K)
  and dbeta as (B, T, H) (the grid's heads axis is sequential there: every
  block of heads adds its columns to one block).  So the phase runs three
  times a step in training (forward, the layer's recomputed forward, the
  group's recomputed forward) and its tile a fourth time inside the
  gradient.

Measured on a TPU v5 lite at (1, 4096, 32, 128) bf16, one group of 64 chunks
(my chip runs, PR 35): `kda_chunk_fwd` 2.73 ms against 7.52 for the XLA form
with its relayouts, `kda_chunk_bwd` 5.82 ms.  Of the forward tile 1.10 ms is
everything but the inverse and the diagonal ratios, the ratios 0.13, and the
inverse 1.49 with two heads' chains side by side (2.25 one head at a time,
3.49 ms the kernel: the MXU's time follows the rows pushed through it; a
doubling that stacks [x; m] against m pushes the same rows and lost, 4.32
against 4.03 at one head a step).  The Kimi cell's step: 1,502 -> 1,155 ms.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import context as _context
from .pallas_kernels import _prec

__all__ = ["kda", "causal_conv"]

_SUB = 16          # rows of a sub-block inside a chunk
# The chunks go in groups of this many, one group after another: a group's
# first phase over all its chunks at once, then its scan.  The backward pass
# keeps the state entering each group and recomputes the group from its
# inputs.  All 256 chunks of the Kimi cell's sequence at once (1 x 16,384
# tokens, 32 heads of 128) need 5.87 GB of temporaries forward + backward (a
# dozen f32 tensors of 268 MB and their cotangents, a state a chunk), and the
# cell's step does not fit the chip beside them (PERF.md section 6, PR 34).
_CHUNKS_AT_ONCE = 64
_F32 = jnp.float32
# Heads of one chunk a grid step of the kernels takes, an even number so that
# their inverses go in pairs.  At (1, 4096, 32, 128) bf16, ms forward /
# backward kernel (my chip run, PR 35, TPU v5 lite): 2 heads 2.73 / 5.82, 4
# heads 2.27 / 5.21, 8 pass the default 16 MiB of scoped VMEM in the backward
# kernel.  Four would buy 30 ms of the cell's 1,155 ms step and cost 3.4 s
# more of tracing and lowering in every process (2.8 s at two, 6.2 at four,
# compiled here for the described chip): the cell's warm set-up is 65 s and
# bounded at 10%.
_HEADS_AT_ONCE = 2


def causal_conv(x, w):
    """Depthwise causal convolution over time: x (B, T, Ch), w (Ch, taps)
    -> (B, T, Ch) with out[t] = sum_j w[:, j] * x[t - (taps - 1) + j] and
    zeros before the sequence; the last tap multiplies x[t] itself.  Taps
    shifted multiply-adds in f32, no bias."""
    taps, t = w.shape[1], x.shape[1]
    # padded in x's dtype, widened tap by tap: an f32 copy of the padded input
    # is 268 MB a projection at the Kimi cell's shapes, and XLA writes it
    padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    out = sum(padded[:, j:j + t].astype(_F32) * w[:, j].astype(_F32)
              for j in range(taps))
    return out.astype(x.dtype)


@jax.custom_vjp
def _unit_lower_inverse(n):
    """(I + n)^-1 for strictly lower triangular n (..., C, C), f32."""
    eye = jnp.eye(n.shape[-1], dtype=n.dtype)
    return jax.lax.linalg.triangular_solve(
        eye + n, jnp.broadcast_to(eye, n.shape), left_side=True, lower=True,
        unit_diagonal=True)


def _unit_lower_inverse_fwd(n):
    x = _unit_lower_inverse(n)
    return x, x


def _unit_lower_inverse_bwd(x, ct):
    # d(X) = -X dN X, so N's cotangent is -X^T ct X^T, kept strictly lower
    xt = jnp.swapaxes(x, -1, -2)
    full = -jnp.einsum("...ab,...bc,...cd->...ad", xt, ct, xt,
                       precision=jax.lax.Precision.HIGHEST)
    return (jnp.tril(full, -1),)


_unit_lower_inverse.defvjp(_unit_lower_inverse_fwd, _unit_lower_inverse_bwd)


def _within_chunks(q, k, v, g, beta):
    """What a chunk computes without the state entering it.  Leading axes
    (N, B, H); q, k, g (..., C, K), v (..., C, V), beta (..., C).  Returns
    (W, U0, Gamma*Q, B, exp(gamma_C - gamma)*K in v's dtype, Gamma_C f32)."""
    lead, (c, kd) = q.shape[:-2], q.shape[-2:]
    sub = min(_SUB, c)
    nsub = c // sub
    hi = jax.lax.Precision.HIGHEST
    gamma = jnp.cumsum(g.astype(_F32), axis=-2)
    qf, kf = q.astype(_F32), k.astype(_F32)

    def blocks(x):
        return x.reshape(lead + (nsub, sub, x.shape[-1]))

    gb, qb, kb = blocks(gamma), blocks(qf), blocks(kf)
    # inside a sub-block: the ratio per (r, i, channel), i <= r
    seen = jnp.tril(jnp.ones((sub, sub), bool))[..., None]
    ratio = jnp.where(seen, jnp.exp(jnp.where(
        seen, gb[..., :, None, :] - gb[..., None, :, :], 0.0)), 0.0)
    a_in = jnp.sum(kb[..., :, None, :] * kb[..., None, :, :] * ratio, axis=-1)
    b_in = jnp.sum(qb[..., :, None, :] * kb[..., None, :, :] * ratio, axis=-1)
    # sub-block I against the sub-blocks before it: two factors <= 1 each
    # against gamma at I's first row, then a matmul; k's and q's rows share
    # the columns' factor
    a_rows, b_rows = [], []
    for i in range(nsub):
        rho = gb[..., i, :1, :]                                # (..., 1, K)
        rows = jnp.exp(gb[..., i, :, :] - rho)                 # (..., sub, K)
        parts_a, parts_b = [a_in[..., i, :, :]], [b_in[..., i, :, :]]
        if i:
            cols = kf[..., :i * sub, :] * jnp.exp(jnp.minimum(
                rho - gamma[..., :i * sub, :], 0.0))
            both = jnp.einsum(
                "...rc,...ic->...ri",
                jnp.concatenate([kb[..., i, :, :] * rows,
                                 qb[..., i, :, :] * rows], axis=-2),
                cols, precision=hi)
            parts_a.insert(0, both[..., :sub, :])
            parts_b.insert(0, both[..., sub:, :])
        after = jnp.zeros(lead + (sub, c - (i + 1) * sub), _F32)
        a_rows.append(jnp.concatenate(parts_a + [after], axis=-1))
        b_rows.append(jnp.concatenate(parts_b + [after], axis=-1))
    a = jnp.tril(jnp.concatenate(a_rows, axis=-2), -1)         # (..., C, C)
    b = jnp.concatenate(b_rows, axis=-2)
    bf = beta.astype(_F32)
    # X Diag(beta): the solve once, for every right-hand side
    xb = _unit_lower_inverse(bf[..., :, None] * a) * bf[..., None, :]
    dt = v.dtype
    gam = jnp.exp(gamma)
    xb = xb.astype(dt)
    w = jnp.einsum("...ri,...ic->...rc", xb, (kf * gam).astype(dt),
                   preferred_element_type=_F32).astype(dt)
    u0 = jnp.einsum("...ri,...iv->...rv", xb, v,
                    preferred_element_type=_F32).astype(dt)
    last = gamma[..., -1:, :]
    return (w, u0, (qf * gam).astype(dt), b.astype(dt),
            (kf * jnp.exp(last - gamma)).astype(dt), jnp.exp(last[..., 0, :]))


# ---------------------------------------------------------------------------
# the first phase as a kernel pair
# ---------------------------------------------------------------------------
_HIGHEST = jax.lax.Precision.HIGHEST


def _dot32(a, b, dims=((1,), (0,))):
    """An f32 matmul of two f32 tiles (contracting `dims`)."""
    return jax.lax.dot_general(a, b, (dims, ((), ())), precision=_HIGHEST,
                               preferred_element_type=_F32)


@jax.custom_vjp
def _dot_native(a, b):
    """a (M, I) x b (I, N) in the operands' dtype with f32 sums: -> f32."""
    return jax.lax.dot_general(
        a, b, (((1,), (0,)), ((), ())), precision=_prec(a.dtype),
        preferred_element_type=_F32)


def _dot_native_fwd(a, b):
    return _dot_native(a, b), (a, b)


def _dot_native_bwd(res, ct):
    # the cotangent arrives rounded to the operands' dtype (the product is
    # cast on its way out), so the transposed products run in it too
    a, b = res
    ct = ct.astype(a.dtype)
    da = jax.lax.dot_general(ct, b, (((1,), (1,)), ((), ())),
                             precision=_prec(a.dtype),
                             preferred_element_type=_F32)
    db = jax.lax.dot_general(a, ct, (((0,), (0,)), ((), ())),
                             precision=_prec(a.dtype),
                             preferred_element_type=_F32)
    return da.astype(a.dtype), db.astype(b.dtype)


_dot_native.defvjp(_dot_native_fwd, _dot_native_bwd)


def _per_head_rhs(m, c):
    """m (C, P C), P heads' (C, C) matrices side by side on the lanes, as
    the right-hand side that multiplies each head's columns by its own
    matrix: block-diagonal (P C, P C)."""
    if m.shape[1] == c:
        return m
    cols = jax.lax.broadcasted_iota(jnp.int32, m.shape, 1)
    return jnp.concatenate(
        [jnp.where((cols >= j * c) & (cols < (j + 1) * c), m, 0.0)
         for j in range(m.shape[1] // c)], axis=0)


@jax.custom_vjp
def _tile_inverses(ns):
    """(I + n)^-1 of each strictly lower triangular tile n (C, C) of a
    tuple, f32: n is nilpotent, so the inverse is the product of
    I + (-n)^(2^k) over 2^k < C, five squarings and five products for C = 64.
    The MXU's time follows the rows pushed through it, not the columns it
    fills, and a 64-wide product fills half of them: the tiles go in pairs,
    side by side on the lanes against a block-diagonal right-hand side, so
    that one push serves two heads (probe 2 in PERF.md section 6, PR 35)."""
    c, out = ns[0].shape[0], []
    for i in range(0, len(ns), 2):
        pair = ns[i:i + 2]
        m = -jnp.concatenate(pair, axis=1)
        rows = jax.lax.broadcasted_iota(jnp.int32, m.shape, 0)
        cols = jax.lax.broadcasted_iota(jnp.int32, m.shape, 1)
        x = sum(jnp.where(rows + j * c == cols, 1.0, 0.0)
                for j in range(len(pair))).astype(_F32) + m
        power = 2
        while power < c:
            m = _dot32(m, _per_head_rhs(m, c))
            x = x + _dot32(x, _per_head_rhs(m, c))
            power *= 2
        out += [x[:, j * c:(j + 1) * c] for j in range(len(pair))]
    return tuple(out)


def _tile_inverses_fwd(ns):
    xs = _tile_inverses(ns)
    return xs, xs


def _tile_inverses_bwd(xs, cts):
    # as `_unit_lower_inverse_bwd`: -X^T ct X^T, strictly lower; ct X^T for
    # a pair at once
    c, out = xs[0].shape[0], []
    rows = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    for i in range(0, len(xs), 2):
        pair = xs[i:i + 2]
        right = _dot32(jnp.concatenate(cts[i:i + 2], axis=1),
                       _per_head_rhs(jnp.concatenate(pair, axis=1), c),
                       ((1,), (1,)))
        for j, x in enumerate(pair):
            full = _dot32(x, right[:, j * c:(j + 1) * c], ((0,), (0,)))
            out.append(jnp.where(rows > cols, -full, 0.0))
    return (tuple(out),)


_tile_inverses.defvjp(_tile_inverses_fwd, _tile_inverses_bwd)


@jax.jit
def _column(g8, k8, q8, gi, ki, a8, b8, hit, seen):
    """Column i of A and B for eight rows of its sub-block: the ratio
    exp(gamma_r - gamma_i) per channel where r >= i (`seen`; None where all
    eight are), summed against k_r k_i and q_r k_i into the lane `hit`.
    Jitted so that a tile's 96 columns a head are 96 calls of two traces
    and not 1,300 operations to trace, differentiate and transpose one by
    one, in every process that builds a step: the kernel pair's trace at
    two heads a grid step is 1.2 s with it and 3.5 s without."""
    ratio = g8 - gi
    if seen is None:
        ratio = jnp.exp(ratio)
    else:
        ratio = jnp.where(seen, jnp.exp(jnp.where(seen, ratio, 0.0)), 0.0)
    t = ratio * ki
    return (jnp.where(hit, jnp.sum(k8 * t, axis=1, keepdims=True), a8),
            jnp.where(hit, jnp.sum(q8 * t, axis=1, keepdims=True), b8))


def _tile_diagonal(gs, ks, qs, lo, c):
    """Rows lo .. lo + sub of A and B inside their own sub-block, (sub, C)
    each with the other columns zero: the ratio per (r, i, channel), i <= r,
    a column i at a time, formed as `_within_chunks` forms it.  The rows go
    eight at a time (an f32 vreg's sublanes): the eight above column i's own
    hold no r >= i and are not computed, and the eight below it need no
    mask."""
    sub = gs.shape[0]
    eight = min(8, sub)
    a_rows, b_rows = [], []
    for r0 in range(0, sub, eight):
        g8, k8, q8 = (x[r0:r0 + eight] for x in (gs, ks, qs))
        row = r0 + jax.lax.broadcasted_iota(jnp.int32, (eight, 1), 0)
        lane = jax.lax.broadcasted_iota(jnp.int32, (eight, c), 1)
        a8 = jnp.zeros((eight, c), _F32)
        b8 = jnp.zeros((eight, c), _F32)
        for i in range(r0 + eight):
            a8, b8 = _column(g8, k8, q8, gs[i:i + 1], ks[i:i + 1], a8, b8,
                             lane == lo + i, None if i <= r0 else row >= i)
        a_rows.append(a8)
        b_rows.append(b8)
    return jnp.concatenate(a_rows, axis=0), jnp.concatenate(b_rows, axis=0)


def _tile_until_inverse(q, k, g, beta, head):
    """`_within_chunks` for ONE (chunk, head) as far as the matrix to
    invert: q, k, g (C, K), beta (C, H) of which column `head` is this
    head's.  Returns (Diag(beta) A, what `_tile_after_inverse` needs).  2-D
    values only, so that it traces inside a Mosaic kernel, and so does its
    `jax.vjp`."""
    c, kd = q.shape
    sub = min(_SUB, c)
    rows = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    # beta as a column and as a row
    mine = jax.lax.broadcasted_iota(jnp.int32, beta.shape, 1) == head
    bcol = jnp.sum(jnp.where(mine, beta.astype(_F32), 0.0), axis=1,
                   keepdims=True)                               # (C, 1)
    brow = jnp.sum(jnp.where(rows == cols, bcol, 0.0), axis=0,
                   keepdims=True)                               # (1, C)
    # the running sum over the chunk's rows, as a matmul
    gamma = _dot32(jnp.where(rows >= cols, 1.0, 0.0).astype(_F32),
                   g.astype(_F32))
    qf, kf = q.astype(_F32), k.astype(_F32)
    a_rows, b_rows = [], []
    for s in range(c // sub):
        lo = s * sub
        gs, ks, qs = (x[lo:lo + sub] for x in (gamma, kf, qf))
        a_s, b_s = _tile_diagonal(gs, ks, qs, lo, c)
        if s:
            # against the sub-blocks before it: two factors <= 1 each
            # against gamma at this one's first row, then a matmul
            rho = gs[:1]
            factor = jnp.exp(gs - rho)
            before = kf[:lo] * jnp.exp(jnp.minimum(rho - gamma[:lo], 0.0))
            before = jnp.concatenate(
                [before, jnp.zeros((c - lo, kd), _F32)], axis=0)
            both = _dot32(jnp.concatenate([ks * factor, qs * factor], axis=0),
                          before, ((1,), (1,)))                 # (2 sub, C)
            a_s = a_s + both[:sub]
            b_s = b_s + both[sub:]
        a_rows.append(a_s)
        b_rows.append(b_s)
    a = jnp.where(rows > cols, jnp.concatenate(a_rows, axis=0), 0.0)
    b = jnp.where(rows >= cols, jnp.concatenate(b_rows, axis=0), 0.0)
    return bcol * a, (brow, b, gamma, qf, kf)


def _tile_after_inverse(x, v, rest):
    """The tile's six values from X = (I + Diag(beta) A)^-1."""
    brow, b, gamma, qf, kf = rest
    dt = v.dtype
    xb = (x * brow).astype(dt)
    gam = jnp.exp(gamma)
    last = gamma[gamma.shape[0] - 1:]
    return (_dot_native(xb, (kf * gam).astype(dt)).astype(dt),
            _dot_native(xb, v).astype(dt), (qf * gam).astype(dt),
            b.astype(dt), (kf * jnp.exp(last - gamma)).astype(dt),
            jnp.exp(last))


def _tiles(qs, ks, vs, gs, beta, first):
    """`_within_chunks` for one chunk and the heads first, first + 1, ...:
    a tuple of q (C, K) a head, and so on; the six values of each head."""
    parts = [_tile_until_inverse(q, k, g, beta, first + j)
             for j, (q, k, g) in enumerate(zip(qs, ks, gs))]
    xs = _tile_inverses(tuple(n for n, _rest in parts))
    return tuple(_tile_after_inverse(x, v, rest)
                 for x, v, (_n, rest) in zip(xs, vs, parts))


def _heads_at_once(h):
    return max(n for n in range(1, _HEADS_AT_ONCE + 1) if h % n == 0)


def _head_tiles(refs, heads):
    """Each ref's block (C, heads * width) as a tuple of (C, width) tiles."""
    return tuple(tuple(r[:, j * (r.shape[1] // heads):
                         (j + 1) * (r.shape[1] // heads)]
                       for j in range(heads)) for r in refs)


def _kda_chunk_fwd_kernel(q, k, v, g, beta, *outs, heads):
    tiles = _tiles(*_head_tiles((q, k, v, g), heads), beta[...],
                   pl.program_id(2) * heads)
    for j, tile in enumerate(tiles):
        for ref, x in zip(outs, tile):
            ref[j] = x


def _kda_chunk_bwd_kernel(q, k, v, g, beta, *refs, heads):
    cts, (dq, dk, dv, dg, dbeta) = refs[:6], refs[6:]
    first = pl.program_id(2) * heads

    # beta's block holds every head's column and is visited once a block of
    # heads: zeroed at the first, each adds its own columns
    @pl.when(first == 0)
    def _():
        dbeta[...] = jnp.zeros_like(dbeta)

    _tiles_out, vjp = jax.vjp(
        functools.partial(_tiles, first=first),
        *_head_tiles((q, k, v, g), heads), beta[...])
    *grads, cbeta = vjp(tuple(tuple(ct[j] for ct in cts)
                              for j in range(heads)))
    for ref, per_head in zip((dq, dk, dv, dg), grads):
        width = ref.shape[1] // heads
        for j, x in enumerate(per_head):
            ref[:, j * width:(j + 1) * width] = x
    dbeta[...] += cbeta


def _chunk_specs(b, t, h, kd, vd, chunk, heads):
    """(grid, the five inputs' specs and the six chunk-major outputs' specs
    and shapes) of both kernels: a grid step is one chunk of one sequence
    and `heads` heads; q, k, v, g are read as (B, T, H K) at the heads' lane
    offset and the outputs written (chunks, B, H, C, .)."""
    n = t // chunk

    def rows(last):
        return pl.BlockSpec((None, chunk, heads * last),
                            lambda ni, bi, hi: (bi, ni, hi))

    def major(r, last):
        return pl.BlockSpec((None, None, heads, r, last),
                            lambda ni, bi, hi: (ni, bi, hi, 0, 0))

    ins = [rows(kd), rows(kd), rows(vd), rows(kd),
           pl.BlockSpec((None, chunk, h), lambda ni, bi, hi: (bi, ni, 0))]
    tiles = [(chunk, kd), (chunk, vd), (chunk, kd), (chunk, chunk),
             (chunk, kd), (1, kd)]
    return (n, b, h // heads), ins, [major(*tile) for tile in tiles], \
        [(n, b, h) + tile for tile in tiles]


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def _kda_chunk_fwd(q, k, v, g, beta, chunk, interpret):
    (b, t, hk), h, dt = q.shape, beta.shape[-1], v.dtype
    kd, vd, heads = hk // h, v.shape[-1] // h, _heads_at_once(h)
    grid, ins, outs, shapes = _chunk_specs(b, t, h, kd, vd, chunk, heads)
    *tiles, gam_c = pl.pallas_call(
        functools.partial(_kda_chunk_fwd_kernel, heads=heads),
        grid=grid, in_specs=ins, out_specs=outs,
        out_shape=[jax.ShapeDtypeStruct(s, dt) for s in shapes[:5]]
        + [jax.ShapeDtypeStruct(shapes[5], _F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",) * 3),
        interpret=interpret, name="kda_chunk_fwd",
    )(q, k, v, g, beta)
    return (*tiles, gam_c.reshape(gam_c.shape[:3] + (kd,)))


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def _kda_chunk_bwd(q, k, v, g, beta, cts, chunk, interpret):
    (b, t, hk), h = q.shape, beta.shape[-1]
    kd, vd, heads = hk // h, v.shape[-1] // h, _heads_at_once(h)
    grid, ins, outs, _shapes = _chunk_specs(b, t, h, kd, vd, chunk, heads)
    *cts, ct_gam = cts
    return tuple(pl.pallas_call(
        functools.partial(_kda_chunk_bwd_kernel, heads=heads),
        grid=grid, in_specs=ins + outs, out_specs=ins,
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype)
                   for x in (q, k, v, g, beta)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret, name="kda_chunk_bwd",
    )(q, k, v, g, beta, *cts, ct_gam.reshape(ct_gam.shape[:3] + (1, kd))))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _within_chunks_kernels(q, k, v, g, beta, chunk, interpret):
    """`_within_chunks` of every chunk of q, k, g (B, T, H K), v (B, T, H V)
    and beta (B, T, H), T a multiple of `chunk`: the same six values,
    chunk-major, from `kda_chunk_fwd`; its gradient is `kda_chunk_bwd`,
    which is given the inputs alone."""
    return _kda_chunk_fwd(q, k, v, g, beta, chunk, interpret)


def _within_chunks_kernels_fwd(q, k, v, g, beta, chunk, interpret):
    return _kda_chunk_fwd(q, k, v, g, beta, chunk, interpret), \
        (q, k, v, g, beta)


def _within_chunks_kernels_bwd(chunk, interpret, inputs, cts):
    return _kda_chunk_bwd(*inputs, cts, chunk, interpret)


_within_chunks_kernels.defvjp(_within_chunks_kernels_fwd,
                              _within_chunks_kernels_bwd)


def _across_chunks(state, chunk):
    """One chunk given the state entering it: (state leaving it, O)."""
    w, u0, qg, b, kdec, gam_c = chunk
    s = state.astype(w.dtype)
    u = (u0.astype(_F32) - jnp.einsum(
        "...rc,...cv->...rv", w, s, preferred_element_type=_F32)
         ).astype(w.dtype)
    o = jnp.einsum("...rc,...cv->...rv", qg, s, preferred_element_type=_F32) \
        + jnp.einsum("...ri,...iv->...rv", b, u, preferred_element_type=_F32)
    state = gam_c[..., :, None] * state + jnp.einsum(
        "...rc,...rv->...cv", kdec, u, preferred_element_type=_F32)
    return state, o.astype(w.dtype)


def _takes_kernels(kd, vd, chunk):
    """The first phase's implementation, from platform and shapes: Mosaic
    wants the heads' channels in multiples of 128 lanes and the chunk's rows
    in multiples of bf16's 16 sublanes."""
    return _context.on_tpu() and kd % 128 == 0 and vd % 128 == 0 \
        and chunk % 16 == 0


def _kda(q, k, v, g, beta, chunk, kernels):
    bsz, t, h, kd = q.shape
    n = t // chunk

    groups = -(-n // _CHUNKS_AT_ONCE)
    while n % groups:
        groups += 1

    if kernels:
        def grouped(x):  # (B, T, H, ...) -> (groups, B, T / groups, H ...)
            return jnp.moveaxis(
                x.reshape(bsz, groups, t // groups, -1), 1, 0)

        g = g.astype(_F32)
        within = functools.partial(_within_chunks_kernels, chunk=chunk,
                                   interpret=not _context.on_tpu())
    else:
        def grouped(x):  # -> (groups, N / groups, B, H, C, ...)
            x = x.reshape((bsz, n, chunk) + x.shape[2:])
            x = jnp.moveaxis(jnp.moveaxis(x, 3, 2), 1, 0)
            return x.reshape((groups, n // groups) + x.shape[1:])

        within = _within_chunks

    def group(state, args):
        return jax.lax.scan(_across_chunks, state, within(*args))

    _last, o = jax.lax.scan(
        jax.checkpoint(group), jnp.zeros((bsz, h, kd, v.shape[-1]), _F32),
        tuple(grouped(x) for x in (q, k, v, g, beta)))
    o = o.reshape((n,) + o.shape[2:])
    # (N, B, H, C, V) -> (B, T, H, V)
    return jnp.moveaxis(jnp.moveaxis(o, 0, 1), 2, 3).reshape(v.shape)


def kda(q, k, v, g, beta, chunk=64):
    """Kimi Delta Attention's recurrence (this module's head) over whole
    sequences from a zero state: o (B, T, H, V) in v's dtype,
    differentiable in all five.  q and k arrive normalised and scaled by the
    layer.  A T that is no multiple of ``chunk`` is padded with rows that
    leave the state alone (k = 0, beta = 0, g = 0) and whose outputs are
    dropped."""
    from .. import telemetry
    if chunk % _SUB:
        raise ValueError(f"chunk must be a multiple of {_SUB}; got {chunk}")
    t = q.shape[1]
    chunk = min(chunk, -(-t // _SUB) * _SUB)
    kernels = _takes_kernels(q.shape[-1], v.shape[-1], chunk)
    telemetry.counter(
        "mxtpu_linear_attention_lowerings", "linear-attention cores traced, "
        "by the implementation taken", labelnames=("path",)
    ).labels(path="pallas_chunk" if kernels else "chunked_scan").inc()
    pad = -t % chunk
    if pad:
        q, k, v, g, beta = (
            jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
            for x in (q, k, v, g, beta))
    o = _kda(q, k, v, g, beta, chunk, kernels)
    return o[:, :t] if pad else o
