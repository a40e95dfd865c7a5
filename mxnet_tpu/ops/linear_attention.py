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
What does not need S_0 is computed for all chunks of a group at once
(`_within_chunks`): A and B, the inverse X = (I + Diag(beta) A)^-1, and
W = X Diag(beta) (Gamma * K), U0 = X Diag(beta) V, so that U = U0 - W S_0.
Then a `lax.scan` over the group's chunks carries S in f32 and does four
matmuls an iteration, batched over (B, H): W S, B U, (Gamma * Q) S and the
state's update (`_across_chunks`); an outer scan carries S from group to
group.  The backward pass is autodiff through both phases: it keeps the
state entering each GROUP and recomputes the group, which keeps one state
per chunk of that group (nothing per token, and never T/C x K x V at once).

Decays.  A ratio exp(gamma_r - gamma_i) with i <= r is at most 1 and is
formed as such: never as Gamma_r * (1 / Gamma_i), whose second factor
overflows f32 after a few strongly decayed tokens.  Inside a chunk the rows
go in sub-blocks of 16.  Between sub-blocks I > J both factors are taken
against gamma at I's first row, exp(gamma_r - rho_I) * exp(rho_I - gamma_i),
each <= 1, so those blocks are matmuls; inside a sub-block the ratio is
formed per (r, i, channel) and summed.  A, B, the inverse and every decay
are f32; W, U0, B and the decayed Q and K go to the inputs' dtype for the
scan's matmuls, which accumulate in f32.

`mxtpu_linear_attention_lowerings{path}` counts the traces, by the
implementation taken ("chunked_scan": there is one).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

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


def _kda(q, k, v, g, beta, chunk):
    bsz, t, h, kd = q.shape
    n = t // chunk

    groups = -(-n // _CHUNKS_AT_ONCE)
    while n % groups:
        groups += 1

    def chunked(x):      # (B, T, H, ...) -> (groups, N / groups, B, H, C, ...)
        x = x.reshape((bsz, n, chunk) + x.shape[2:])
        x = jnp.moveaxis(jnp.moveaxis(x, 3, 2), 1, 0)
        return x.reshape((groups, n // groups) + x.shape[1:])

    def group(state, args):
        return jax.lax.scan(_across_chunks, state, _within_chunks(*args))

    _last, o = jax.lax.scan(
        jax.checkpoint(group), jnp.zeros((bsz, h, kd, v.shape[-1]), _F32),
        tuple(chunked(x) for x in (q, k, v, g, beta)))
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
    telemetry.counter(
        "mxtpu_linear_attention_lowerings", "linear-attention cores traced, "
        "by the implementation taken", labelnames=("path",)
    ).labels(path="chunked_scan").inc()
    if chunk % _SUB:
        raise ValueError(f"chunk must be a multiple of {_SUB}; got {chunk}")
    t = q.shape[1]
    chunk = min(chunk, -(-t // _SUB) * _SUB)
    pad = -t % chunk
    if pad:
        q, k, v, g, beta = (
            jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
            for x in (q, k, v, g, beta))
    o = _kda(q, k, v, g, beta, chunk)
    return o[:, :t] if pad else o
