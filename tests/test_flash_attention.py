"""Flash attention Pallas kernel vs dense oracle (interpret mode on CPU).

Round 6 adds the recipe-realistic tier: key-padding masks, additive
bias, and in-kernel attention dropout, fwd AND bwd.  The dropout tests
lean on `attn_dropout_mask` — the exact keep/rescale mask the kernels
regenerate from the threefry seed — multiplied into the dense oracle:
if the backward kernels drew different bits than the forward, the
gradient-parity assertions here could not hold.
"""
import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu.ops.pallas_kernels import (attn_dropout_mask,
                                          flash_attention)


def _dense(q, k, v, causal=False, scale=None, mask=None, bias=None,
           keep=None):
    d = q.shape[-1]
    sc = d ** -0.5 if scale is None else scale
    s = onp.einsum("bhqd,bhkd->bhqk", q, k) * sc
    if bias is not None:
        s = s + bias
    t = s.shape[-1]
    if causal:
        cm = onp.tril(onp.ones((t, t), bool))
        s = onp.where(cm, s, -1e30)
    if mask is not None:
        s = onp.where(mask[:, None, None, :] != 0, s, -1e30)
    s = s - s.max(-1, keepdims=True)
    p = onp.exp(s)
    p /= p.sum(-1, keepdims=True)
    if keep is not None:
        p = p * onp.asarray(keep)
    return onp.einsum("bhqk,bhkd->bhqd", p, v)


def _qkv(seed, b=1, h=2, t=64, d=8):
    rng = onp.random.RandomState(seed)
    return [rng.randn(b, h, t, d).astype(onp.float32) for _ in range(3)]


def _prefix_mask(lens, t):
    return (onp.arange(t)[None, :] < onp.asarray(lens)[:, None]).astype(
        onp.int32)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_matches_dense(causal):
    onp.random.seed(0)
    b, h, t, d = 2, 3, 64, 16
    q = onp.random.randn(b, h, t, d).astype(onp.float32)
    k = onp.random.randn(b, h, t, d).astype(onp.float32)
    v = onp.random.randn(b, h, t, d).astype(onp.float32)
    out = flash_attention(mx.np.array(q), mx.np.array(k), mx.np.array(v),
                          causal=causal, block_q=32, block_k=16)
    expect = _dense(q, k, v, causal=causal)
    assert onp.allclose(out.asnumpy(), expect, atol=2e-5), \
        onp.abs(out.asnumpy() - expect).max()


def test_flash_gradients_match_dense():
    """The custom VJP (blockwise recompute) must equal dense-attention
    gradients."""
    onp.random.seed(1)
    import jax
    import jax.numpy as jnp

    from mxnet_tpu import autograd
    qn, kn, vn = _qkv(1, 1, 2, 32, 8)
    q, k, v = (mx.np.array(a) for a in (qn, kn, vn))
    for a in (q, k, v):
        a.attach_grad()
    with autograd.record():
        loss = (flash_attention(q, k, v, causal=True, block_q=16,
                                block_k=16) ** 2).sum()
    loss.backward()

    def dense_loss(qj, kj, vj):
        d = qj.shape[-1]
        s = jnp.einsum("bhqd,bhkd->bhqk", qj, kj) * d ** -0.5
        t = s.shape[-1]
        mask = jnp.tril(jnp.ones((t, t), bool))
        s = jnp.where(mask, s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        return (jnp.einsum("bhqk,bhkd->bhqd", p, vj) ** 2).sum()

    gq, gk, gv = jax.grad(dense_loss, argnums=(0, 1, 2))(qn, kn, vn)
    for got, expect in [(q.grad, gq), (k.grad, gk), (v.grad, gv)]:
        assert onp.allclose(got.asnumpy(), onp.asarray(expect), atol=1e-3), \
            onp.abs(got.asnumpy() - onp.asarray(expect)).max()


@pytest.mark.parametrize("bq,bk", [(16, 32), (32, 16), (64, 64)])
def test_flash_causal_block_skip_grads(bq, bk):
    """Causal kernels skip fully-masked blocks (fwd: ki past the diagonal,
    dkv: qi before it).  Unequal block shapes exercise the last_ki /
    first_qi index arithmetic in both directions; gradients must still
    match the dense oracle exactly."""
    onp.random.seed(3)
    import jax
    import jax.numpy as jnp

    from mxnet_tpu import autograd
    qn, kn, vn = _qkv(3, 1, 2, 64, 8)
    q, k, v = (mx.np.array(a) for a in (qn, kn, vn))
    for a in (q, k, v):
        a.attach_grad()
    with autograd.record():
        loss = (flash_attention(q, k, v, causal=True, block_q=bq,
                                block_k=bk) ** 2).sum()
    loss.backward()

    def dense_loss(qj, kj, vj):
        d = qj.shape[-1]
        s = jnp.einsum("bhqd,bhkd->bhqk", qj, kj) * d ** -0.5
        t = s.shape[-1]
        mask = jnp.tril(jnp.ones((t, t), bool))
        s = jnp.where(mask, s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        return (jnp.einsum("bhqk,bhkd->bhqd", p, vj) ** 2).sum()

    gq, gk, gv = jax.grad(dense_loss, argnums=(0, 1, 2))(qn, kn, vn)
    for got, expect in [(q.grad, gq), (k.grad, gk), (v.grad, gv)]:
        assert onp.allclose(got.asnumpy(), onp.asarray(expect), atol=1e-3), \
            onp.abs(got.asnumpy() - onp.asarray(expect)).max()


def test_flash_causal_lse_matches_dense():
    """Causal lse (what ring attention's peeled diagonal step merges on)
    must equal the dense masked logsumexp even with skipped blocks."""
    import jax.numpy as jnp

    from mxnet_tpu.ops.pallas_kernels import flash_attention_with_lse
    onp.random.seed(4)
    b, h, t, d = 1, 2, 64, 8
    qn, kn, vn = _qkv(4, b, h, t, d)
    _out, lse = flash_attention_with_lse(
        jnp.asarray(qn), jnp.asarray(kn), jnp.asarray(vn), causal=True,
        block_q=16, block_k=16, interpret=True)
    s = onp.einsum("bhqd,bhkd->bhqk", qn, kn) * d ** -0.5
    mask = onp.tril(onp.ones((t, t), bool))
    s = onp.where(mask, s, -1e30)
    m = s.max(-1)
    expect = m + onp.log(onp.exp(s - m[..., None]).sum(-1))
    assert onp.allclose(onp.asarray(lse), expect, atol=2e-5), \
        onp.abs(onp.asarray(lse) - expect).max()


def test_flash_rejects_indivisible_length():
    q = mx.np.ones((1, 1, 50, 8))
    with pytest.raises(ValueError, match="divide"):
        flash_attention(q, q, q, block_q=32, block_k=32)


# ---------------------------------------------------------------------------
# round 6: key-padding masks
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("bq,bk", [(16, 16), (16, 32), (32, 16)])
def test_flash_padding_mask_matches_dense(causal, bq, bk):
    """Ragged prefix lengths (incl. one full row and one short row):
    fwd parity against the dense masked softmax, every block shape
    exercising the kend skip/clamp arithmetic."""
    qn, kn, vn = _qkv(10, 3, 2, 64, 8)
    mask = _prefix_mask([17, 64, 1], 64)
    out = flash_attention(mx.np.array(qn), mx.np.array(kn),
                          mx.np.array(vn), causal=causal,
                          mask=mx.np.array(mask), block_q=bq, block_k=bk)
    expect = _dense(qn, kn, vn, causal=causal, mask=mask)
    assert onp.allclose(out.asnumpy(), expect, atol=2e-5), \
        onp.abs(out.asnumpy() - expect).max()


def test_flash_padding_mask_non_prefix_holes():
    """The kernel is correct for ARBITRARY per-key masks, not just
    contiguous prefixes — kend only bounds the skip, holes inside it
    mask in-block."""
    qn, kn, vn = _qkv(11, 2, 2, 64, 8)
    rng = onp.random.RandomState(12)
    mask = (rng.rand(2, 64) > 0.4).astype(onp.int32)
    mask[:, 40:] = 0  # padded tail on top of the holes
    mask[:, 0] = 1    # keep every row non-empty
    out = flash_attention(mx.np.array(qn), mx.np.array(kn),
                          mx.np.array(vn), mask=mx.np.array(mask),
                          block_q=16, block_k=16)
    expect = _dense(qn, kn, vn, mask=mask)
    assert onp.allclose(out.asnumpy(), expect, atol=2e-5), \
        onp.abs(out.asnumpy() - expect).max()


def test_flash_padding_mask_gradients_match_dense():
    import jax
    import jax.numpy as jnp

    qn, kn, vn = _qkv(13, 2, 2, 64, 8)
    mask = jnp.asarray(_prefix_mask([23, 64], 64))

    def flash_loss(q, k, v):
        return (flash_attention(q, k, v, mask=mask, block_q=16,
                                block_k=32) ** 2).sum()

    def dense_loss(q, k, v):
        d = q.shape[-1]
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * d ** -0.5
        s = jnp.where(mask[:, None, None, :] != 0, s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        return (jnp.einsum("bhqk,bhkd->bhqd", p, v) ** 2).sum()

    gf = jax.grad(flash_loss, argnums=(0, 1, 2))(qn, kn, vn)
    gd = jax.grad(dense_loss, argnums=(0, 1, 2))(qn, kn, vn)
    for name, a, b in zip("qkv", gf, gd):
        assert onp.allclose(onp.asarray(a), onp.asarray(b), atol=1e-4), \
            (name, onp.abs(onp.asarray(a) - onp.asarray(b)).max())


def test_flash_fully_masked_rows_zero_and_nan_free():
    """Rows with NO valid key: exact-0 output, finite zero gradients
    (the dense softmax degenerates to uniform there — the kernel's 0 is
    the deliberate, documented semantics; loss code masks those rows
    out anyway)."""
    import jax

    qn, kn, vn = _qkv(14, 2, 2, 64, 8)
    mask = _prefix_mask([0, 37], 64)  # batch row 0 entirely padded
    import jax.numpy as jnp
    mj = jnp.asarray(mask)
    out = flash_attention(qn, kn, vn, mask=mj, block_q=16, block_k=16)
    assert not bool(jnp.isnan(out).any())
    assert bool((out[0] == 0).all())

    gq, gk, gv = jax.grad(
        lambda q, k, v: (flash_attention(
            q, k, v, mask=mj, block_q=16, block_k=16) ** 2).sum(),
        argnums=(0, 1, 2))(qn, kn, vn)
    for g in (gq, gk, gv):
        assert bool(jnp.isfinite(g).all())
        assert bool((g[0] == 0).all())


def test_flash_masked_lse_matches_dense():
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops.pallas_kernels import flash_attention_with_lse
    qn, kn, vn = _qkv(15, 2, 2, 64, 8)
    mask = _prefix_mask([29, 64], 64)
    _out, lse = flash_attention_with_lse(
        jnp.asarray(qn), jnp.asarray(kn), jnp.asarray(vn),
        mask=jnp.asarray(mask), block_q=16, block_k=16)
    s = onp.einsum("bhqd,bhkd->bhqk", qn, kn) * 8 ** -0.5
    s = onp.where(mask[:, None, None, :] != 0, s, -1e30)
    expect = onp.asarray(jax.scipy.special.logsumexp(s, axis=-1))
    assert onp.allclose(onp.asarray(lse), expect, atol=2e-5)


def test_flash_kend_skip_bounds():
    """The mask-driven skip machinery: `_kend` finds 1 + the last valid
    key (0 when none; holes don't shrink it), and the q-major fetch
    clamp pins every K-block index past it to the last valid block —
    the no-HBM-traffic contract for padded tails."""
    import jax.numpy as jnp

    from mxnet_tpu.ops.pallas_kernels import _ck_factory, _kend
    mi = jnp.asarray(onp.array([
        [1, 1, 1, 0, 0, 0, 0, 0],    # prefix 3 -> kend 3
        [1, 0, 1, 0, 1, 0, 0, 0],    # holes, last valid at 4 -> kend 5
        [0, 0, 0, 0, 0, 0, 0, 0],    # empty -> kend 0
        [1, 1, 1, 1, 1, 1, 1, 1],    # full -> kend 8
    ], onp.int32))
    assert onp.asarray(_kend(mi)).tolist() == [3, 5, 0, 8]

    ck = _ck_factory(block_q=2, block_k=2, causal=False, masked=True, nh=1)
    kend = jnp.asarray([3, 0], jnp.int32)
    # batch row 0 (kend=3): last valid K block is 1; blocks 2,3 clamp to 1
    assert [int(ck(0, 0, ki, (kend,))) for ki in range(4)] == [0, 1, 1, 1]
    # batch row 1 (kend=0): everything clamps to block 0
    assert [int(ck(1, 0, ki, (kend,))) for ki in range(4)] == [0, 0, 0, 0]


# ---------------------------------------------------------------------------
# round 6: in-kernel attention dropout
# ---------------------------------------------------------------------------
def test_threefry_matches_jax_reference():
    """The in-kernel generator IS threefry2x32: bit-identical to jax's
    own implementation for the same key/counter words."""
    import jax.numpy as jnp
    from jax._src import prng as _jprng

    from mxnet_tpu.ops.pallas_kernels import _threefry2x32
    key = jnp.array([0xDEADBEEF, 0x12345678], jnp.uint32)
    cnt = jnp.arange(8, dtype=jnp.uint32)
    ref = onp.asarray(_jprng.threefry_2x32(key, cnt))[:4]
    mine = onp.asarray(_threefry2x32(
        jnp.uint32(0xDEADBEEF), jnp.uint32(0x12345678),
        cnt[:4], cnt[4:]))
    assert (ref == mine).all()


def test_flash_dropout_matches_dense_with_regenerated_mask():
    """THE fwd/bwd-determinism test: a dense oracle multiplied by
    `attn_dropout_mask` (the mask the kernels regenerate from the seed)
    must match flash EXACTLY — forward values AND dq/dk/dv.  If the
    backward kernels drew different bits than the forward, the gradient
    parity here could not hold."""
    import jax
    import jax.numpy as jnp

    qn, kn, vn = _qkv(16, 2, 2, 64, 8)
    key = jax.random.key(42)
    rate = 0.3
    keep = attn_dropout_mask(key, 2, 2, 64, 64, rate)
    # marginal keep rate ~ 1 - rate
    assert abs(float((keep > 0).mean()) - (1 - rate)) < 0.03
    # rescale factor exact on survivors
    assert onp.allclose(onp.unique(onp.asarray(keep)),
                        [0.0, 1.0 / (1 - rate)])

    def flash_loss(q, k, v):
        return (flash_attention(q, k, v, dropout=rate, key=key,
                                block_q=16, block_k=32) ** 2).sum()

    def dense_loss(q, k, v):
        d = q.shape[-1]
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * d ** -0.5
        p = jax.nn.softmax(s, axis=-1) * keep
        return (jnp.einsum("bhqk,bhkd->bhqd", p, v) ** 2).sum()

    out_f = flash_attention(qn, kn, vn, dropout=rate, key=key,
                            block_q=16, block_k=32)
    expect = _dense(qn, kn, vn, keep=onp.asarray(keep))
    assert onp.allclose(onp.asarray(out_f), expect, atol=2e-5), \
        onp.abs(onp.asarray(out_f) - expect).max()

    gf = jax.grad(flash_loss, argnums=(0, 1, 2))(qn, kn, vn)
    gd = jax.grad(dense_loss, argnums=(0, 1, 2))(qn, kn, vn)
    for name, a, b in zip("qkv", gf, gd):
        assert onp.allclose(onp.asarray(a), onp.asarray(b), atol=1e-4), \
            (name, onp.abs(onp.asarray(a) - onp.asarray(b)).max())


def test_flash_dropout_deterministic_per_key():
    import jax

    qn, kn, vn = _qkv(17, 1, 2, 32, 8)
    k1, k2 = jax.random.key(1), jax.random.key(2)
    a = flash_attention(qn, kn, vn, dropout=0.5, key=k1,
                        block_q=16, block_k=16)
    b = flash_attention(qn, kn, vn, dropout=0.5, key=k1,
                        block_q=16, block_k=16)
    c = flash_attention(qn, kn, vn, dropout=0.5, key=k2,
                        block_q=16, block_k=16)
    assert (onp.asarray(a) == onp.asarray(b)).all()
    assert (onp.asarray(a) != onp.asarray(c)).any()
    # block shape does NOT change the mask (positions are global): the
    # regenerated-mask contract holds across any fwd/bwd block pairing
    d = flash_attention(qn, kn, vn, dropout=0.5, key=k1,
                        block_q=32, block_k=8)
    assert onp.allclose(onp.asarray(a), onp.asarray(d), atol=2e-5)


def test_flash_dropout_with_mask_and_causal():
    """All three in-kernel effects stack; parity vs the dense oracle
    carrying the same regenerated dropout mask."""
    import jax

    qn, kn, vn = _qkv(18, 2, 2, 64, 8)
    key = jax.random.key(9)
    mask = _prefix_mask([41, 64], 64)
    keep = attn_dropout_mask(key, 2, 2, 64, 64, 0.25)
    out = flash_attention(qn, kn, vn, causal=True,
                          mask=onp.asarray(mask, onp.int32),
                          dropout=0.25, key=key, block_q=16, block_k=16)
    expect = _dense(qn, kn, vn, causal=True, mask=mask,
                    keep=onp.asarray(keep))
    assert onp.allclose(onp.asarray(out), expect, atol=2e-5), \
        onp.abs(onp.asarray(out) - expect).max()


def test_flash_dropout_requires_key():
    q = mx.np.ones((1, 1, 16, 8))
    with pytest.raises(ValueError, match="key"):
        flash_attention(q, q, q, dropout=0.5)
    with pytest.raises(ValueError, match="dropout"):
        flash_attention(q, q, q, dropout=1.5)


# ---------------------------------------------------------------------------
# round 6: additive bias
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("bshape", [(64, 64), (2, 64, 64), (3, 2, 64, 64)])
def test_flash_bias_matches_dense(bshape):
    """ALiBi-style additive score bias, every broadcast layout the
    BlockSpec index maps support ((T,T), per-head, full)."""
    qn, kn, vn = _qkv(19, 3, 2, 64, 8)
    rng = onp.random.RandomState(20)
    bias = rng.randn(*bshape).astype(onp.float32) * 0.5
    out = flash_attention(qn, kn, vn, bias=bias, block_q=16, block_k=32)
    expect = _dense(qn, kn, vn,
                    bias=bias.reshape((1,) * (4 - bias.ndim) + bshape))
    assert onp.allclose(onp.asarray(out), expect, atol=2e-5), \
        onp.abs(onp.asarray(out) - expect).max()


def test_flash_bias_is_constant_no_gradient():
    """The documented stop-gradient contract: q/k/v grads match the
    dense oracle, bias receives exact zeros."""
    import jax
    import jax.numpy as jnp

    qn, kn, vn = _qkv(21, 1, 2, 32, 8)
    bias = onp.random.RandomState(22).randn(32, 32).astype(onp.float32)

    def flash_loss(q, b):
        return (flash_attention(q, kn, vn, bias=b, block_q=16,
                                block_k=16) ** 2).sum()

    gq, gb = jax.grad(flash_loss, argnums=(0, 1))(qn, bias)
    assert bool((jnp.asarray(gb) == 0).all())

    def dense_loss(q):
        d = q.shape[-1]
        s = jnp.einsum("bhqd,bhkd->bhqk", q, kn) * d ** -0.5 + bias
        p = jax.nn.softmax(s, axis=-1)
        return (jnp.einsum("bhqk,bhkd->bhqd", p, vn) ** 2).sum()

    gq_d = jax.grad(dense_loss)(qn)
    assert onp.allclose(onp.asarray(gq), onp.asarray(gq_d), atol=1e-4)


# ---------------------------------------------------------------------------
# MultiHeadAttention dispatch
# ---------------------------------------------------------------------------
def test_mha_use_flash_matches_einsum_path():
    """MultiHeadAttention(use_flash=True) equals the einsum path."""
    from mxnet_tpu.models import MultiHeadAttention
    onp.random.seed(2)
    x = mx.np.array(onp.random.randn(2, 32, 16).astype(onp.float32))
    a = MultiHeadAttention(16, 4, dropout=0.0)
    a.initialize()
    b = MultiHeadAttention(16, 4, dropout=0.0, use_flash=True)
    b.initialize()
    a(x)  # materialize deferred shapes before copying weights
    b(x)
    for name, p in a.collect_params().items():
        b.collect_params()[name].set_data(p.data())
    ya = a(x).asnumpy()
    yb = b(x).asnumpy()
    assert onp.allclose(ya, yb, atol=2e-5), onp.abs(ya - yb).max()


def test_mha_use_flash_masked_matches_einsum_path():
    """use_flash=True with a ragged key-padding mask equals the dense
    masked path (round-6 contract: the mask runs in-kernel, no fallback
    and no error)."""
    from mxnet_tpu.models import MultiHeadAttention
    onp.random.seed(5)
    x = mx.np.array(onp.random.randn(2, 32, 16).astype(onp.float32))
    mask = mx.np.array(_prefix_mask([9, 32], 32))
    a = MultiHeadAttention(16, 4, dropout=0.0)
    a.initialize()
    b = MultiHeadAttention(16, 4, dropout=0.0, use_flash=True)
    b.initialize()
    a(x, mask)
    b(x, mask)
    for name, p in a.collect_params().items():
        b.collect_params()[name].set_data(p.data())
    ya = a(x, mask).asnumpy()
    yb = b(x, mask).asnumpy()
    assert onp.allclose(ya, yb, atol=2e-5), onp.abs(ya - yb).max()


def test_mha_flash_dropout_train_mode():
    """use_flash=True + dropout>0 constructs (the old ValueError is
    gone); dropout is inert at inference, active and stream-seeded in
    train mode."""
    from mxnet_tpu import autograd
    from mxnet_tpu.models import MultiHeadAttention
    onp.random.seed(6)
    x = mx.np.array(onp.random.randn(1, 32, 16).astype(onp.float32))
    mha = MultiHeadAttention(16, 4, dropout=0.3, use_flash=True)
    mha.initialize()
    y1 = mha(x).asnumpy()
    y2 = mha(x).asnumpy()
    assert (y1 == y2).all()  # inference: no dropout
    mx.random.seed(7)
    with autograd.record():
        t1 = mha(x).asnumpy()
    mx.random.seed(7)
    with autograd.record():
        t2 = mha(x).asnumpy()
    with autograd.record():
        t3 = mha(x).asnumpy()
    assert (t1 == t2).all()       # deterministic under the seeded stream
    assert (t1 != t3).any()       # fresh draw -> different mask
    assert (t1 != y1).any()       # train mode actually drops


def test_mha_flash_dispatch_path_assertion(monkeypatch):
    """Acceptance: use_flash='auto' + dropout>0 + padding mask
    dispatches to the flash kernel past the crossover — asserted on the
    actual call path (npx.flash_attention), not just the policy."""
    from mxnet_tpu import autograd
    from mxnet_tpu.models import transformer as tr

    monkeypatch.setattr(tr, "on_tpu", lambda: True)
    # shrink the crossover so the interpret-mode kernel stays test-sized
    monkeypatch.setattr(tr, "FLASH_AUTO_MIN_T_TRAINING", 32)
    calls = []
    real = tr.npx.flash_attention

    def spy(*args, **kwargs):
        calls.append(kwargs)
        kwargs["interpret"] = True  # on_tpu is faked; stay runnable
        return real(*args, **kwargs)

    monkeypatch.setattr(tr.npx, "flash_attention", spy)
    mha = tr.MultiHeadAttention(16, 4, dropout=0.2)
    mha.initialize()
    x = mx.np.array(onp.random.randn(2, 32, 16).astype(onp.float32))
    mask = mx.np.array(_prefix_mask([17, 32], 32))
    with autograd.record():
        out = mha(x, mask)
    assert calls, "auto policy silently fell back to the dense path"
    assert calls[0].get("dropout") == 0.2
    assert calls[0].get("mask") is not None
    assert not onp.isnan(out.asnumpy()).any()


def test_flash_small_sequence_blocks_clamp():
    # T smaller than the default blocks: clamps to T
    q = mx.np.ones((1, 1, 8, 4))
    out = flash_attention(q, q, q)
    assert out.shape == (1, 1, 8, 4)


# within budget the whole T is one block; over budget, 384 = 2^7 * 3 steps
# down to its largest pow2 divisor <= want, never up.  1000 = 8 * 125 has no
# pow2 divisor >= 128: a whole-T fallback would build a single-block kernel
# whose (T, T) f32 score tile blows VMEM at large T, so the walk goes down to
# 8.  Odd T has no pow2 divisor at all: degenerate single block
@pytest.mark.parametrize("t,want,block", [
    (384, 512, 384), (384, 256, 128), (384, 64, 64), (1000, 512, 8),
    (999, 512, 999)])
def test_pick_block(t, want, block):
    from mxnet_tpu.ops.pallas_kernels import _pick_block
    assert _pick_block(t, want) == block
    assert t % block == 0


# what a launch without explicit blocks runs with: the pairs the parent
# (1f4c461) resolved on this machine, where its tile cache always missed
@pytest.mark.parametrize("t,blocks", [
    (128, (128, 128)), (256, (256, 256)), (384, (384, 384)),
    (512, (512, 512)), (1000, (8, 1000)), (1024, (512, 1024)),
    (2048, (512, 1024)), (4096, (512, 1024)), (8192, (512, 1024))])
def test_resolve_default_blocks(t, blocks):
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops import pallas_kernels as pk
    qd = jax.ShapeDtypeStruct((2, 4, t, 64), jnp.bfloat16)
    bq, bk, _, _ = pk._resolve(qd, None, None, None, False)
    assert (bq, bk) == blocks
    assert t % bq == 0 and t % bk == 0
    assert bq <= pk._BLOCK_TARGET_Q and bk <= pk._BLOCK_TARGET_K
    # one explicit block leaves the other at its default
    assert pk._resolve(qd, 8, None, None, False)[:2] == (8, bk)


def test_flash_q_tiling_bit_parity_fwd_bwd():
    """A q-block split is bitwise-identical to the default tile in the
    forward output and dq (the q split never changes their accumulation
    order; block_k is pinned because the k split reassociates the softmax
    accumulation).  dk/dv DO accumulate across q-blocks — there a block_q
    change reorders the f32 sums, so the contract is allclose."""
    import jax
    import jax.numpy as jnp

    q, k, v = (jnp.asarray(x) for x in _qkv(3, t=256, d=16))

    def run(**blocks):
        def f(q, k, v):
            return flash_attention(q, k, v, **blocks).astype(jnp.float32).sum()
        return (flash_attention(q, k, v, **blocks),
                jax.grad(f, argnums=(0, 1, 2))(q, k, v))

    out_d, (gq_d, gk_d, gv_d) = run()              # default: one 256 block
    out_s, (gq_s, gk_s, gv_s) = run(block_q=64, block_k=256)
    assert onp.array_equal(onp.asarray(out_d), onp.asarray(out_s))
    assert onp.array_equal(onp.asarray(gq_d), onp.asarray(gq_s))
    for ga, gb in ((gk_d, gk_s), (gv_d, gv_s)):
        onp.testing.assert_allclose(onp.asarray(ga), onp.asarray(gb),
                                    rtol=1e-5, atol=1e-6)


def test_mha_auto_flash_policy(monkeypatch):
    """use_flash='auto' (the default) picks flash only on TPU, above the
    measured crossover; key-padding masks and attention dropout are
    ELIGIBLE (round 6), full attention masks are not."""
    from mxnet_tpu.models import transformer as tr

    mha = tr.MultiHeadAttention(64, 4, dropout=0.0)
    assert mha._use_flash == "auto"
    # off-TPU (this CI): auto never picks the interpret-mode kernel
    assert not mha._flash_now(tr.FLASH_AUTO_MIN_T, None)
    monkeypatch.setattr(tr, "on_tpu", lambda: True)
    assert not mha._flash_now(tr.FLASH_AUTO_MIN_T - 128, None)
    assert mha._flash_now(tr.FLASH_AUTO_MIN_T, None)
    pad_mask = mx.np.ones((2, tr.FLASH_AUTO_MIN_T))
    assert mha._flash_now(tr.FLASH_AUTO_MIN_T, pad_mask)  # (B, S): eligible
    full_mask = mx.np.ones((2, 8, 8))
    assert not mha._flash_now(tr.FLASH_AUTO_MIN_T, full_mask)  # (B,T,S): no
    assert not mha._flash_now(tr.FLASH_AUTO_MIN_T, object())   # unknown: no
    assert not mha._flash_now(tr.FLASH_AUTO_MIN_T + 1, None)  # not /128
    dropped = tr.MultiHeadAttention(64, 4, dropout=0.1)
    assert dropped._flash_now(tr.FLASH_AUTO_MIN_T, None)  # dropout eligible
    forced = tr.MultiHeadAttention(64, 4, use_flash=False)
    assert not forced._flash_now(tr.FLASH_AUTO_MIN_T, None)
    # under an active tape the (lower) training crossover applies: the
    # flash fwd+bwd kernels beat dense from FLASH_AUTO_MIN_T_TRAINING up
    from mxnet_tpu import autograd
    t_train = tr.FLASH_AUTO_MIN_T_TRAINING
    assert t_train < tr.FLASH_AUTO_MIN_T  # measured relationship
    assert not mha._flash_now(t_train, None)  # no tape: inference tier
    with autograd.record():
        assert mha._flash_now(t_train, None)
        assert not mha._flash_now(t_train - 128, None)
    # predict-mode gradients (record(train_mode=False)) still backprop
    with autograd.record(train_mode=False):
        assert mha._flash_now(t_train, None)
    # compiled traces force recording off and declare the backward
    # explicitly (_scoped_forward(backward=True))
    from mxnet_tpu.ops.invoke import set_backward_expected
    prev = set_backward_expected(True)
    try:
        assert mha._flash_now(t_train, None)
    finally:
        set_backward_expected(prev)
    assert not mha._flash_now(t_train, None)
    import pytest as _pt
    with _pt.raises(ValueError, match="use_flash"):
        tr.MultiHeadAttention(64, 4, use_flash=1)


def test_hybridize_jit_cache_keys_on_backward():
    """A predict-mode tape around a hybridized call must compile its own
    program (the flash policy differs), not reuse the inference one."""
    import mxnet_tpu as mx
    from mxnet_tpu import autograd
    from mxnet_tpu.models import transformer as tr

    mha = tr.MultiHeadAttention(16, 2, dropout=0.0)
    mha.initialize()
    x = mx.np.ones((1, 8, 16))
    mha.hybridize()
    mha(x)                                    # inference trace
    assert (False, False) in mha._jit_cache
    x2 = mx.np.ones((1, 8, 16))
    x2.attach_grad()
    with autograd.record(train_mode=False):   # predict-mode gradients
        out = mha(x2)
    out.backward()
    assert (False, True) in mha._jit_cache
    assert x2.grad is not None


@pytest.mark.parametrize("axes,heads_split", [({"dp": 4}, False),
                                              ({"dp": 2, "tp": 2}, True)],
                         ids=["dp4", "dp2.tp2"])
def test_flash_under_mesh_scope_matches_unsharded(axes, heads_split):
    """Traced inside a `mesh_scope` (a mesh-sharded FusedTrainStep) the
    kernel launches once per device over its share of (batch, heads):
    masked forward and gradients equal the one-device call's, and each
    shard draws its own dropout mask at the asked keep-rate."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from mxnet_tpu.parallel.mesh import make_mesh, mesh_scope

    mesh = make_mesh(axes, devices=jax.devices()[:4])
    q, k, v = (jnp.asarray(a) for a in _qkv(3, b=4, h=2, t=64, d=8))
    mask = jnp.asarray(_prefix_mask([64, 40, 17, 5], 64))
    spec = P("dp", "tp" if heads_split else None)
    qs, ks, vs = (jax.device_put(a, NamedSharding(mesh, spec))
                  for a in (q, k, v))
    ms = jax.device_put(mask, NamedSharding(mesh, P("dp")))

    def grads(q, k, v, mask, **kw):
        return jax.value_and_grad(
            lambda q, k, v: (flash_attention(
                q, k, v, mask=mask, block_q=32, block_k=32, **kw) ** 2).sum(),
            argnums=(0, 1, 2))(q, k, v)

    want = grads(q, k, v, mask)
    with mesh_scope(mesh, ("dp",)):
        got = jax.jit(grads)(qs, ks, vs, ms)
        # one query batch-broadcast, v == 1: a row's output is its kept
        # weight mass over keep (~1), and equal rows tell masks apart
        same = jax.device_put(jnp.broadcast_to(q[:1], q.shape), qs.sharding)
        dropped = jax.jit(lambda q, v: flash_attention(
            q, q, v, dropout=0.5, key=jax.random.key(1),
            block_q=32, block_k=32))(same, jnp.ones_like(vs))
    for a, b in zip(jax.tree_util.tree_leaves(want),
                    jax.tree_util.tree_leaves(got)):
        assert onp.allclose(a, b, rtol=1e-5, atol=1e-5)
    dropped = onp.asarray(dropped)[..., 0]
    assert onp.isfinite(dropped).all()
    assert abs(dropped.mean() - 1.0) < 0.05
    # batch rows 0 and 2 are the first (batch, head) of two different
    # shards: one seed for all shards would repeat one mask there
    assert not onp.allclose(dropped[0], dropped[2])
