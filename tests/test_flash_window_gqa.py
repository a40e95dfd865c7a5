"""Flash attention with fewer key-value heads than query heads and a
sliding window (interpret mode on the CPU) against dense f32 attention:
forward, dq, dk, dv.  Sizes are tiny; T is no multiple of the window."""
import jax
import jax.numpy as jnp
import numpy as onp
import pytest

from mxnet_tpu.ops.pallas_kernels import (attn_dropout_mask, flash_attention,
                                          flash_attention_with_lse)


def dense(q, k, v, causal, window, mask=None, keep=None):
    """Plain softmax attention; query head i reads key-value head
    i // (H/Hkv); key j is visible to query t iff 0 <= t - j < window."""
    group = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    t = q.shape[2]
    s = jnp.einsum("bhtd,bhsd->bhts", q, k) * q.shape[-1] ** -0.5
    d = jnp.arange(t)[:, None] - jnp.arange(t)[None, :]
    seen = jnp.ones((t, t), bool)
    if causal:
        seen = d >= 0
    if window is not None:
        seen = seen & (d < window)
    seen = seen[None, None]
    if mask is not None:
        seen = seen & (mask[:, None, None, :] != 0)
    p = jax.nn.softmax(jnp.where(seen, s, -1e30), axis=-1)
    if keep is not None:
        p = p * keep
    return jnp.einsum("bhts,bhsd->bhtd", p, v)


def qkv(b, h, hkv, t, d, seed=0):
    kq, kk, kv, kc = jax.random.split(jax.random.key(seed), 4)
    return (jax.random.normal(kq, (b, h, t, d), jnp.float32),
            jax.random.normal(kk, (b, hkv, t, d), jnp.float32),
            jax.random.normal(kv, (b, hkv, t, d), jnp.float32),
            jax.random.normal(kc, (b, h, t, d), jnp.float32))


CASES = [
    # h, hkv, t, window, block_q, block_k
    pytest.param(4, 2, 96, 40, 32, 32, id="gqa2-window40-t96"),
    pytest.param(4, 1, 64, 24, 16, 32, id="mqa-window24-wide-k"),
    pytest.param(2, 2, 64, 24, 32, 16, id="mha-window24-wide-q"),
    pytest.param(8, 2, 64, None, 32, 32, id="gqa4-causal"),
    pytest.param(4, 2, 64, 1, 16, 16, id="gqa2-window1"),
    pytest.param(4, 2, 48, 100, 16, 16, id="gqa2-window-wider-than-t"),
]


@pytest.mark.parametrize("h,hkv,t,window,bq,bk", CASES)
def test_forward_and_gradients_match_dense(h, hkv, t, window, bq, bk):
    q, k, v, ct = qkv(2, h, hkv, t, 8)

    def flash(q, k, v):
        return flash_attention(q, k, v, causal=True, window=window,
                               block_q=bq, block_k=bk, interpret=True)

    out, vjp = jax.vjp(flash, q, k, v)
    ref, ref_vjp = jax.vjp(lambda q, k, v: dense(q, k, v, True, window),
                           q, k, v)
    onp.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-5)
    for got, want, name in zip(vjp(ct), ref_vjp(ct), ("dq", "dk", "dv")):
        assert got.shape == want.shape, name
        onp.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4,
                                    err_msg=name)


def test_gqa_noncausal_with_padding_mask():
    q, k, v, ct = qkv(2, 4, 2, 64, 8, seed=1)
    mask = (jnp.arange(64)[None, :] < jnp.array([[40], [64]])).astype(jnp.int32)

    def flash(q, k, v):
        return flash_attention(q, k, v, mask=mask, block_q=16, block_k=16,
                               interpret=True)

    out, vjp = jax.vjp(flash, q, k, v)
    ref, ref_vjp = jax.vjp(
        lambda q, k, v: dense(q, k, v, False, None, mask), q, k, v)
    onp.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-5)
    for got, want in zip(vjp(ct), ref_vjp(ct)):
        onp.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


def test_gqa_window_dropout_regenerates_one_mask():
    """The dk+dv kernel walks (key-value head, query head of the group);
    its dropout bits must be those of the query head, as in the forward."""
    q, k, v, ct = qkv(1, 4, 2, 32, 8, seed=2)
    key = jax.random.key(7)
    keep = attn_dropout_mask(key, 1, 4, 32, 32, 0.25)

    def flash(q, k, v):
        return flash_attention(q, k, v, causal=True, window=12, dropout=0.25,
                               key=key, block_q=16, block_k=16, interpret=True)

    out, vjp = jax.vjp(flash, q, k, v)
    ref, ref_vjp = jax.vjp(
        lambda q, k, v: dense(q, k, v, True, 12, keep=keep), q, k, v)
    onp.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-5)
    for got, want in zip(vjp(ct), ref_vjp(ct)):
        onp.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


def test_lse_of_a_window_merges_like_dense():
    q, k, v, _ = qkv(1, 2, 1, 32, 8, seed=3)
    _out, lse = flash_attention_with_lse(q, k, v, causal=True, window=10,
                                         block_q=16, block_k=16,
                                         interpret=True)
    s = jnp.einsum("bhtd,bhsd->bhts", q, jnp.repeat(k, 2, 1)) * 8 ** -0.5
    d = jnp.arange(32)[:, None] - jnp.arange(32)[None, :]
    want = jax.nn.logsumexp(jnp.where((d >= 0) & (d < 10), s, -jnp.inf), -1)
    onp.testing.assert_allclose(lse, want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("kwargs,match", [
    (dict(window=8), "needs causal"),
    (dict(causal=True, window=0), "needs causal"),
])
def test_window_needs_causal(kwargs, match):
    q, k, v, _ = qkv(1, 2, 2, 16, 8)
    with pytest.raises(ValueError, match=match):
        flash_attention(q, k, v, interpret=True, **kwargs)


def test_head_counts_must_divide():
    q, k, v, _ = qkv(1, 4, 3, 16, 8)
    with pytest.raises(ValueError, match="Hkv dividing"):
        flash_attention(q, k, v, interpret=True)
