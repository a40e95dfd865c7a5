"""Kimi-Linear's block set (ops/linear_attention.py, the two head sizes of the
flash kernels, the sigmoid router, models/decoder.py's KimiDeltaAttention,
LatentAttention and SwiGLU) against the plain f32 reference
(models/reference/kimi_linear.py) on seeded weights, at tiny sizes on the
CPU: the published layers 1-5 (KDA + dense | KDA, KDA, MLA, KDA + experts)."""
import functools
import math
import os

import jax
import jax.numpy as jnp
import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu.models import CausalLMLoss, DecoderLM
from mxnet_tpu.models.reference import kimi_linear as ref
from mxnet_tpu.ops import linear_attention as la
from mxnet_tpu.ops.pallas_kernels import flash_attention
from mxnet_tpu.parallel import RoutedExperts, moe

from chipbench import run as bench

CFGMOD = bench.load_py(os.path.join(bench.HERE, "configs", "kimi_linear_48b_a3b.py"))
CFG = {"hidden_size": 32, "num_hidden_layers": 5, "first_k_dense_replace": 1,
       "intermediate_size": 48, "rms_norm_eps": 1e-5, "vocab_size": 50,
       "linear_attn_config": {"kda_layers": [1, 2, 3, 5, 6, 7], "full_attn_layers": [4, 8],
                              "num_heads": 2, "head_dim": 16, "short_conv_kernel_size": 4},
       "num_attention_heads": 2, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
       "v_head_dim": 16, "kv_lora_rank": 12, "mla_use_nope": True, "q_lora_rank": None,
       "num_experts": 4, "num_experts_routed": 8, "ep_rank": 1, "num_experts_per_token": 2,
       "moe_intermediate_size": 16, "num_shared_experts": 1, "num_expert_group": 1,
       "topk_group": 1, "moe_router_activation_func": "sigmoid", "moe_renormalize": True,
       "routed_scaling_factor": 2.446, "dtype": "float32", "remat": False}
B, T = 2, 40          # T is no multiple of 16: the core pads


# ---------------------------------------------------------------------------
# the chunked scan against the literal recurrence
# ---------------------------------------------------------------------------
def kda_inputs(t, strong, dtype=jnp.float32, b=2, h=3, k=32, v=16, seed=0):
    ks = jax.random.split(jax.random.key(seed), 5)
    q = jax.random.normal(ks[0], (b, t, h, k))
    kk = jax.random.normal(ks[1], (b, t, h, k))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * k ** -0.5
    kk = kk / jnp.linalg.norm(kk, axis=-1, keepdims=True)
    vv = jax.random.normal(ks[2], (b, t, h, v))
    # weak: a channel keeps 90-99.9% a token; strong: g near -20 a token, where
    # 1 / Gamma overflows f32 after five tokens
    g = -jnp.exp(0.3 * jax.random.normal(ks[3], (b, t, h, k))) * (20.0 if strong else 0.03)
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (b, t, h)))
    return q.astype(dtype), kk.astype(dtype), vv.astype(dtype), g, beta


def literal(*args):
    with jax.default_matmul_precision("highest"):
        return ref.delta_rule(*(a.astype(jnp.float32) for a in args))


@pytest.mark.parametrize("strong", [False, True], ids=["weak-decay", "strong-decay"])
@pytest.mark.parametrize("chunks", [1, 3, 8])
def test_chunked_kda_equals_the_literal_recurrence_forward_and_backward(chunks, strong):
    args = kda_inputs(64 * chunks, strong)
    if strong:   # the form this replaces: 1 / Gamma is not finite here
        assert not onp.isfinite(onp.asarray(1.0 / jnp.exp(jnp.cumsum(args[3], axis=1)))).all()
    onp.testing.assert_allclose(la.kda(*args), literal(*args), rtol=1e-4, atol=2e-6)

    def loss(fn):
        return lambda *a: jnp.sum(jnp.sin(3.0 * fn(*a)))
    got = jax.grad(loss(la.kda), argnums=(0, 1, 2, 3, 4))(*args)
    want = jax.grad(loss(literal), argnums=(0, 1, 2, 3, 4))(*args)
    for name, g, w in zip("q k v g beta".split(), got, want):
        assert onp.isfinite(onp.asarray(g)).all(), name
        scale = float(jnp.abs(w).max())
        assert scale > 0, name
        # under the strong decay g's own gradient is sums of terms near e^-20:
        # f32 noise of the other terms' size stands beside exact zeros
        onp.testing.assert_allclose(g, w, rtol=2e-3, err_msg=name,
                                    atol=(2e-3 if name == "g" else 2e-4) * scale)


@pytest.mark.parametrize("t", [5, 40, 100], ids=["T5", "T40", "T100"])
def test_kda_pads_a_ragged_length_with_rows_that_leave_the_state_alone(t):
    args = kda_inputs(t, False, seed=2)
    onp.testing.assert_allclose(la.kda(*args), literal(*args), rtol=1e-4, atol=2e-6)
    onp.testing.assert_allclose(la.kda(*args, chunk=16), literal(*args), rtol=1e-4, atol=2e-6)


def test_kda_in_bf16_stays_near_the_f32_recurrence():
    args = kda_inputs(192, False, jnp.bfloat16, seed=3)
    got = la.kda(*args)
    assert got.dtype == jnp.bfloat16
    want = literal(*args)
    err = jnp.abs(got.astype(jnp.float32) - want)
    assert float(err.max()) < 0.02 * float(jnp.abs(want).max())
    grads = jax.grad(lambda *a: jnp.sum(la.kda(*a).astype(jnp.float32) ** 2),
                     argnums=(0, 1, 2, 3, 4))(*args)
    want_g = jax.grad(lambda *a: jnp.sum(literal(*a) ** 2), argnums=(0, 1, 2, 3, 4))(*args)
    for g, w in zip(grads, want_g):
        g, w = g.astype(jnp.float32).ravel(), w.astype(jnp.float32).ravel()
        assert float(g @ w / (jnp.linalg.norm(g) * jnp.linalg.norm(w))) > 0.995


def test_kda_rejects_a_chunk_that_sub_blocks_do_not_tile():
    with pytest.raises(ValueError, match="multiple of 16"):
        la.kda(*kda_inputs(48, False), chunk=24)


@pytest.mark.parametrize("kernels", [False, True], ids=["chunked_scan", "pallas_chunk"])
def test_kda_counts_its_lowering(monkeypatch, kernels):
    """One trace raises exactly one label: the path the first phase took."""
    from mxnet_tpu import telemetry
    monkeypatch.setattr(la, "_takes_kernels", lambda kd, vd, chunk: kernels)

    def count(path):
        return telemetry.default_registry().get_sample_value(
            "mxtpu_linear_attention_lowerings", {"path": path}) or 0.0
    paths = ("chunked_scan", "pallas_chunk")
    before = [count(p) for p in paths]
    jax.eval_shape(lambda *a: la.kda(*a), *kda_inputs(16, False))    # a trace of its own
    assert [count(p) - b for p, b in zip(paths, before)] == [1.0 - kernels, 1.0 * kernels]


def test_the_kernels_are_taken_on_tpu_where_the_shapes_tile(monkeypatch):
    assert not la._takes_kernels(128, 128, 64)               # this process computes on the CPU
    monkeypatch.setattr(la._context, "on_tpu", lambda: True)
    assert la._takes_kernels(128, 128, 64) and la._takes_kernels(256, 128, 16)
    assert not la._takes_kernels(64, 128, 64) and not la._takes_kernels(128, 192, 64)


# ---------------------------------------------------------------------------
# the first phase's kernel pair, interpreted, against its XLA form
# ---------------------------------------------------------------------------
CHUNK = 32     # two sub-blocks: the ratios inside one, the matmul between them


def chunk_major(x, chunk=CHUNK):
    """(B, T, H, ...) -> (T / chunk, B, H, chunk, ...), what `_within_chunks` takes."""
    b, t = x.shape[:2]
    x = x.reshape((b, t // chunk, chunk) + x.shape[2:])
    return jnp.moveaxis(jnp.moveaxis(x, 3, 2), 1, 0)


def xla_first_phase(*args):
    return la._within_chunks(*(chunk_major(x) for x in args))


def kernel_first_phase(*args):
    b, t = args[0].shape[:2]                                  # heads side by side on the lanes
    return la._within_chunks_kernels(*(x.reshape(b, t, -1) for x in args), CHUNK, True)


def _outputs_and_gradients(fn):
    """`fn`'s six outputs and the five gradients of a fixed weighted sum of them, as one jitted
    program (the decays of a case share it)."""
    def both(*args):
        outs = fn(*args)
        return sum(jnp.sum(jax.random.normal(jax.random.key(7 + i), o.shape) * o.astype(jnp.float32))
                   for i, o in enumerate(outs)), outs
    return jax.jit(jax.value_and_grad(both, argnums=(0, 1, 2, 3, 4), has_aux=True))


_XLA_FIRST_PHASE = _outputs_and_gradients(xla_first_phase)
_KERNEL_FIRST_PHASE = _outputs_and_gradients(kernel_first_phase)


def first_phase_agrees(args, dtype):
    """All six outputs and all five gradients of the kernel pair against the XLA form's."""
    (_, want), grads_want = _XLA_FIRST_PHASE(*args)
    (_, got), grads_got = _KERNEL_FIRST_PHASE(*args)
    # bf16: an output may fall on the other side of a rounding (one unit in the last place of
    # eight bits), and a cotangent that was rounded differently moves a gradient by as much
    rel = 1e-5 if dtype == jnp.float32 else 2.0 ** -7
    for kind, names, wants, gots in (("output", "W U0 GammaQ B Kdec GammaC".split(), want, got),
                                     ("gradient", "q k v g beta".split(), grads_want, grads_got)):
        for name, w, g in zip(names, wants, gots):
            assert g.shape == w.shape and g.dtype == w.dtype, (kind, name)
            w, g = onp.asarray(w, onp.float32), onp.asarray(g, onp.float32)
            assert onp.isfinite(g).all(), (kind, name)
            tol = rel * (4 if kind == "gradient" else 1)
            if (kind, name) == ("gradient", "g"):
                # sums of terms near e^-20 under the strong decay: f32 noise of the other
                # terms' size beside exact zeros (the recurrence's test allows g the same)
                tol = max(tol, 2e-3)
            onp.testing.assert_allclose(g, w, rtol=tol, atol=tol * max(float(onp.abs(w).max()), 1e-30),
                                        err_msg=f"{kind} {name}")


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("chunks,b", [(1, 1), (3, 1), (8, 1), (2, 2)],
                         ids=["1-chunk", "3-chunks", "8-chunks", "2-sequences"])
@pytest.mark.parametrize("strong", [False, True], ids=["weak-decay", "strong-decay"])
def test_the_kernel_pair_equals_the_xla_first_phase(strong, chunks, b, dtype):
    """`kda_chunk_fwd` / `kda_chunk_bwd` interpreted on the CPU on the tiles the XLA form takes:
    one head a grid step (h = 1, its inverse alone) and, with two sequences, four heads in two
    steps of a pair (the inverses side by side; beta's column picked, its gradient summed over
    the steps)."""
    first_phase_agrees(kda_inputs(CHUNK * chunks, strong, dtype, b=b, h=1 if b == 1 else 4,
                                  seed=chunks), dtype)


def _sin_loss_and_output(fn):
    def both(*args):
        o = fn(*args)
        return jnp.sum(jnp.sin(3.0 * o)), o
    return jax.jit(jax.value_and_grad(both, argnums=(0, 1, 2, 3, 4), has_aux=True))


# one program for both decays: jitted once here, traced under the first case's patches
_KERNEL_PATH = _sin_loss_and_output(functools.partial(la.kda, chunk=CHUNK))


@pytest.mark.parametrize("strong", [False, True], ids=["weak-decay", "strong-decay"])
def test_kda_on_the_kernel_path_equals_the_literal_recurrence(monkeypatch, strong):
    """`kda` end to end with the first phase in the kernels (interpreted, a pair of heads a grid
    step), over more chunks than a group holds (180 tokens are padded to six chunks of 32, in
    three groups of two): the tolerances of the XLA path's test."""
    monkeypatch.setattr(la, "_takes_kernels", lambda kd, vd, chunk: True)
    monkeypatch.setattr(la, "_CHUNKS_AT_ONCE", 2)
    args = kda_inputs(180, strong, h=2, seed=4)
    (_loss, o), got = _KERNEL_PATH(*args)
    onp.testing.assert_allclose(o, literal(*args), rtol=1e-4, atol=2e-6)
    (_loss, _o), want = _sin_loss_and_output(literal)(*args)
    for name, g, w in zip("q k v g beta".split(), got, want):
        assert onp.isfinite(onp.asarray(g)).all(), name
        scale = float(jnp.abs(w).max())
        onp.testing.assert_allclose(g, w, rtol=2e-3, err_msg=name,
                                    atol=(2e-3 if name == "g" else 2e-4) * scale)


@pytest.mark.parametrize("fn", [la.causal_conv, ref.short_conv], ids=["system", "reference"])
def test_causal_conv_moves_nothing_backwards_in_time(fn):
    rng = onp.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(2, 12, 6)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(6, 4)), jnp.float32)
    y = fn(x, w)
    # by hand: out[t] = sum_j w[:, j] x[t - 3 + j], zeros before the sequence
    want = onp.zeros((2, 12, 6))
    for t in range(12):
        for j in range(4):
            if t - 3 + j >= 0:
                want[:, t] += onp.asarray(w)[:, j] * onp.asarray(x)[:, t - 3 + j]
    onp.testing.assert_allclose(y, want, rtol=1e-5, atol=1e-6)
    # a change at time 7 reaches outputs 7..10 and nothing before
    y2 = fn(x.at[:, 7].add(1.0), w)
    changed = onp.abs(onp.asarray(y2 - y)).max(axis=(0, 2)) > 0
    assert list(onp.nonzero(changed)[0]) == [7, 8, 9, 10]
    # and the gradient of output t reaches inputs t-3..t only
    g = jax.grad(lambda x: jnp.sum(fn(x, w)[:, 5]))(x)
    assert list(onp.nonzero(onp.abs(onp.asarray(g)).max(axis=(0, 2)) > 0)[0]) == [2, 3, 4, 5]


# ---------------------------------------------------------------------------
# flash attention with q.k and v heads of different sizes
# ---------------------------------------------------------------------------
def dense_attention(q, k, v, scale):
    group = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    t = q.shape[2]
    s = jnp.einsum("bhtd,bhsd->bhts", q, k) * scale
    p = jax.nn.softmax(jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf), axis=-1)
    return jnp.einsum("bhts,bhsd->bhtd", p, v)


@pytest.mark.parametrize("hkv", [4, 2], ids=["mha", "grouped"])
def test_flash_with_two_head_sizes_equals_dense_attention_forward_and_backward(hkv):
    b, h, t, d, dv = 1, 4, 256, 48, 32
    ks = jax.random.split(jax.random.key(1), 3)
    q = jax.random.normal(ks[0], (b, h, t, d))
    k = jax.random.normal(ks[1], (b, hkv, t, d))
    v = jax.random.normal(ks[2], (b, hkv, t, dv))

    def flash(q, k, v):
        return flash_attention(q, k, v, causal=True, block_q=64, block_k=128, interpret=True)

    out = flash(q, k, v)
    assert out.shape == (b, h, t, dv)
    with jax.default_matmul_precision("highest"):
        want = dense_attention(q, k, v, d ** -0.5)       # the default scale is D^-1/2, q.k's
        want_g = jax.grad(lambda *a: jnp.sum(jnp.sin(dense_attention(*a, d ** -0.5))),
                          argnums=(0, 1, 2))(q, k, v)
    onp.testing.assert_allclose(out, want, rtol=1e-4, atol=1e-5)
    got_g = jax.grad(lambda *a: jnp.sum(jnp.sin(flash(*a))), argnums=(0, 1, 2))(q, k, v)
    for name, g, w in zip("qkv", got_g, want_g):
        assert g.shape == w.shape
        onp.testing.assert_allclose(g, w, rtol=1e-3, atol=2e-5, err_msg=name)


def test_flash_with_one_head_size_is_what_a_wider_v_gives_in_its_first_channels():
    """v padded with zero channels: the first D channels of the result are the
    D = Dv kernels' (to rounding: a matmul of another width sums in another
    order), the padded ones exactly zero, and so are their gradients.  That the
    D = Dv programs are bit for bit the parent's was checked once against the
    parent's tree (PERF.md, PR 34): six variants, outputs and three gradients."""
    b, h, t, d = 1, 2, 128, 32
    ks = jax.random.split(jax.random.key(2), 3)
    q, k, v = (jax.random.normal(kk, (b, h, t, d)) for kk in ks)
    kw = dict(causal=True, window=40, block_q=64, block_k=64, interpret=True)
    one = flash_attention(q, k, v, **kw)
    padded = jnp.pad(v, ((0, 0),) * 3 + ((0, 16),))
    wide = flash_attention(q, k, padded, **kw)
    onp.testing.assert_allclose(wide[..., :d], one, rtol=1e-5, atol=1e-6)
    assert not onp.asarray(wide[..., d:]).any()
    dv = jax.grad(lambda v: jnp.sum(flash_attention(q, k, v, **kw)[..., :d] ** 2))(padded)
    want = jax.grad(lambda v: jnp.sum(flash_attention(q, k, v, **kw) ** 2))(v)
    onp.testing.assert_allclose(dv[..., :d], want, rtol=1e-4, atol=1e-5)
    assert not onp.asarray(dv[..., d:]).any()


def test_flash_still_refuses_keys_and_values_of_different_lengths():
    q = jnp.zeros((1, 2, 64, 16))
    with pytest.raises(ValueError, match="Hkv dividing"):
        flash_attention(q, q, jnp.zeros((1, 2, 32, 8)), interpret=True)


# ---------------------------------------------------------------------------
# the sigmoid router
# ---------------------------------------------------------------------------
def test_sigmoid_router_chooses_by_biased_score_and_weighs_by_the_unbiased_one():
    m = jnp.eye(3, 4, dtype=jnp.float32)                       # three tokens, U = 4
    router = jnp.asarray([[2.0, 1.0, 0.0, -1.0, -2.0],
                          [0.0, 0.0, 3.0, 0.0, 0.0],
                          [0.1, 0.2, 0.3, 0.4, 0.5],
                          [0.0, 0.0, 0.0, 0.0, 0.0]], jnp.float32)
    bias = jnp.asarray([0.0, 0.0, 0.0, 0.0, 1.0], jnp.float32)  # lifts expert 4 into every choice
    s = onp.asarray(jax.nn.sigmoid(m @ router))
    top_e, top_w = moe.route_top_k(m, router, 2, "sigmoid", bias, True, 2.446)
    assert [sorted(r) for r in onp.asarray(top_e).tolist()] == [[0, 4], [2, 4], [3, 4]]
    for t in range(3):
        chosen = s[t, onp.asarray(top_e)[t]]
        onp.testing.assert_allclose(top_w[t], chosen / chosen.sum() * 2.446, rtol=1e-6)
    plain_e, plain_w = moe.route_top_k(m, router, 2, "sigmoid", None, False, 1.0)
    assert [sorted(r) for r in onp.asarray(plain_e).tolist()] == [[0, 1], [2, 0], [3, 4]] \
        or [sorted(r) for r in onp.asarray(plain_e).tolist()][0] == [0, 1]
    onp.testing.assert_allclose(plain_w[0], s[0, onp.asarray(plain_e)[0]], rtol=1e-6)


@pytest.mark.parametrize("scoring", ["softmax", "sigmoid"])
def test_given_picks_keep_the_routers_own_weights(scoring):
    rng = onp.random.default_rng(4)
    m = jnp.asarray(rng.normal(size=(5, 4)), jnp.float32)
    router = jnp.asarray(rng.normal(size=(4, 6)), jnp.float32)
    own_e, own_w = moe.route_top_k(m, router, 2, scoring)
    same_e, same_w = moe.route_top_k(m, router, 2, scoring, picks=own_e)
    assert onp.array_equal(own_e, same_e)
    onp.testing.assert_allclose(same_w, own_w, rtol=1e-6)
    other = (own_e + 1) % 6
    got_e, got_w = moe.route_top_k(m, router, 2, scoring, picks=other)
    score = (jax.nn.softmax if scoring == "softmax" else jax.nn.sigmoid)(m @ router)
    chosen = onp.take_along_axis(onp.asarray(score), onp.asarray(other), axis=-1)
    assert onp.array_equal(got_e, other)
    onp.testing.assert_allclose(got_w, chosen / chosen.sum(-1, keepdims=True), rtol=1e-5)


def test_no_gradient_reaches_the_correction_bias_and_the_router_gets_one():
    rng = onp.random.default_rng(3)
    m = jnp.asarray(rng.normal(size=(6, 4)), jnp.float32)
    router = jnp.asarray(rng.normal(size=(4, 5)), jnp.float32)
    bias = jnp.asarray(rng.normal(size=(5,)) * 0.1, jnp.float32)

    def f(router, bias):
        _e, w = moe.route_top_k(m, router, 2, "sigmoid", bias, True, 2.446)
        return jnp.sum(w * jnp.arange(1.0, 3.0))
    d_router, d_bias = jax.grad(f, argnums=(0, 1))(router, bias)
    assert not onp.asarray(d_bias).any() and onp.asarray(d_router).any()
    layer = RoutedExperts(4, 8, 5, 2, scoring="sigmoid", scaling_factor=2.446)
    assert layer.correction_bias.grad_req == "null" and layer.correction_bias.shape == (5,)
    assert not hasattr(RoutedExperts(4, 8, 5, 2), "correction_bias")   # softmax: as it was
    with pytest.raises(ValueError, match="scoring"):
        RoutedExperts(4, 8, 5, 2, scoring="tanh")


# ---------------------------------------------------------------------------
# the model against the reference
# ---------------------------------------------------------------------------
def build(experts_held=4, ep_rank=1, seed=3, **kw):
    cfg = dict(CFG, num_experts=experts_held, ep_rank=ep_rank)
    mx.random.seed(seed)
    model = DecoderLM(**dict(CFGMOD.model_arguments(cfg), **kw))
    model.initialize()
    # a bias that changes some choices, so that the test can tell it is applied
    for name in model._layer_names[1:]:
        bias = getattr(model, name).experts.correction_bias
        bias.data()._rebind(jnp.asarray(
            onp.random.default_rng(8).normal(size=bias.shape) * 0.05, jnp.float32))
    return model


def reference_params(model):
    p = {k: jnp.asarray(v.data()._data, jnp.float32)
         for k, v in model.collect_params().items()}
    return CFGMOD.reference_params(p, CFG)


REF_CFG = CFGMOD.reference_config(CFG)


def ids(seed=0):
    return onp.random.default_rng(seed).integers(0, CFG["vocab_size"], (B, T))


SHARES = [pytest.param(8, 0, id="all-8-experts"), pytest.param(4, 1, id="experts-4-to-7"),
          pytest.param(2, 0, id="experts-0-and-1")]


def test_the_cut_builds_the_five_kinds_of_layer():
    model = build()
    assert model.layer_kinds == [("kda", "dense"), ("kda", "sparse"), ("kda", "sparse"),
                                 ("latent_attention", "sparse"), ("kda", "sparse")]
    assert ref.layer_kinds(REF_CFG) == [(a.replace("latent_attention", "mla"), f)
                                        for a, f in model.layer_kinds]
    assert hasattr(model.layer0, "mlp") and not hasattr(model.layer0, "experts")
    assert hasattr(model.layer3, "shared") and model.layer3.experts.first_expert == 4
    with pytest.raises(ValueError, match="layer_types"):
        DecoderLM(8, 8, ["convolution"])
    with pytest.raises(ValueError, match="mlp_layer_types"):
        DecoderLM(8, 8, ["kda"], mlp_layer_types=["conv"], kda=dict(num_heads=1, head_dim=8))


@pytest.mark.parametrize("held,rank", SHARES)
def test_logits_and_loss_match_the_reference(held, rank):
    model = build(held, rank)
    x = ids()
    logits = model(mx.np.array(x, dtype="int32")).asnumpy()
    params = reference_params(model)
    share = {"experts_held": held, "ep_rank": rank}
    want = ref.logits(params, jnp.asarray(x), REF_CFG, **share)
    onp.testing.assert_allclose(logits, want, rtol=2e-4, atol=2e-5)
    loss = CausalLMLoss(model)(mx.np.array(x, dtype="int32")).asnumpy()
    onp.testing.assert_allclose(loss, ref.loss(params, jnp.asarray(x), REF_CFG, **share),
                                rtol=1e-5)
    # the bias took part in the choice: without it the reference reads otherwise
    no_bias = dict(params, layers=[dict(lp, bias=jnp.zeros_like(lp["bias"])) if "bias" in lp
                                   else lp for lp in params["layers"]])
    if held == 8:
        assert float(jnp.abs(ref.logits(no_bias, jnp.asarray(x), REF_CFG, **share)
                             - want).max()) > 1e-4


def _system_grads(model, x):
    mod = CausalLMLoss(model)
    mod.hybridize()
    with mx.autograd.record():
        loss = mod(mx.np.array(x, dtype="int32"))
    loss.backward()
    return float(loss.asnumpy()), {
        k: p.grad().asnumpy() for k, p in model.collect_params().items()
        if p.grad_req != "null"}


_GRAD_CACHE = {}


def _grads_both():
    if not _GRAD_CACHE:
        model = build(4, 1)
        x = ids(1)
        loss, got = _system_grads(model, x)
        want_loss, want = ref.loss_and_grads(reference_params(model), jnp.asarray(x), REF_CFG,
                                             experts_held=4, ep_rank=1)
        # the reference's gradients under the system's names: the same mapping,
        # applied to the gradient tree, backwards
        class _Name(str):
            T = property(lambda self: _Name(self + "^T"))

        theirs = {}
        index = CFGMOD.reference_params({k: _Name(k) for k in model.collect_params()}, CFG)
        for path, name in jax.tree_util.tree_leaves_with_path(index):
            g = want
            for step in path:
                g = g[step.key if hasattr(step, "key") else step.idx]
            theirs[name.removesuffix("^T")] = g.T if name.endswith("^T") else g
        _GRAD_CACHE.update(loss=loss, got=got, want_loss=float(want_loss), want=theirs)
    return _GRAD_CACHE


KDA_PARAMS = ("q_conv k_conv v_conv A_log dt_bias o_norm q_proj.weight k_proj.weight "
              "v_proj.weight f_a.weight f_b.weight b_proj.weight g_a.weight g_b.weight g_b.bias "
              "o_proj.weight").split()
MLA_PARAMS = "q_proj.weight kv_a.weight kv_norm.gamma kv_b.weight o_proj.weight".split()
SPARSE = ("experts.router experts.gate experts.up experts.down shared.gate.weight "
          "shared.up.weight shared.down.weight").split()
PARAM_NAMES = ["embed.weight", "norm.gamma", "head.weight"] + [
    f"layer{l}.{rest}" for l in range(5) for rest in (
        ["attend.norm.gamma", "ffn_norm.gamma"]
        + ["attend.attention." + n for n in (MLA_PARAMS if l == 3 else KDA_PARAMS)]
        + (["mlp.gate.weight", "mlp.up.weight", "mlp.down.weight"] if l == 0 else SPARSE))]


@pytest.mark.parametrize("name", PARAM_NAMES)
def test_gradient_of_every_parameter_matches_the_reference(name):
    both = _grads_both()
    assert abs(both["loss"] - both["want_loss"]) < 1e-5
    want = onp.asarray(both["want"][name])
    scale = onp.abs(want).max()
    assert scale > 0, "a parameter without a gradient tests nothing"
    onp.testing.assert_allclose(both["got"][name], want, rtol=3e-3, atol=3e-4 * scale)


def test_grad_names_cover_every_trainable_parameter_and_not_the_bias():
    got = _grads_both()["got"]
    assert sorted(PARAM_NAMES) == sorted(got)
    assert not any("correction_bias" in k or "expert_load" in k for k in got)


def test_the_ranks_routed_parts_and_the_shared_expert_once_add_up_to_the_uncut_layer():
    """16 experts over 4 ranks: what each rank's layer gives for its own
    experts, minus the shared expert that every rank computes alike, summed
    over the ranks, plus that shared expert once, is the uncut reference's FFN."""
    cfg = dict(CFG, num_experts_routed=16, num_hidden_layers=2)
    rng = onp.random.default_rng(5)
    h = jnp.asarray(rng.normal(size=(1, 24, 32)), jnp.float32)
    whole_params = ref.init_params(jax.random.key(4), CFGMOD.reference_config(cfg), std=0.3)
    lp = dict(whole_params["layers"][1],
              bias=jnp.asarray(rng.normal(size=(16,)) * 0.05, jnp.float32))
    ref_cfg = CFGMOD.reference_config(cfg)
    with jax.default_matmul_precision("highest"):
        want = ref.ffn(h, lp, ref_cfg, 16, 0)
        shared = ref.swiglu(ref.rms_norm(h, lp["norm2"], 1e-5), lp["shared_gate"],
                            lp["shared_up"], lp["shared_down"])
    total, rows = shared, 0
    for rank in range(4):
        mx.random.seed(0)
        model = DecoderLM(**CFGMOD.model_arguments(dict(cfg, num_experts=4, ep_rank=rank)))
        model.initialize()
        layer, held = model.layer1, slice(4 * rank, 4 * rank + 4)
        for param, value in (
                (layer.ffn_norm.gamma, lp["norm2"]), (layer.experts.router, lp["router"]),
                (layer.experts.correction_bias, lp["bias"]),
                (layer.experts.gate, lp["gate"][held]), (layer.experts.up, lp["up"][held]),
                (layer.experts.down, lp["down"][held]),
                (layer.shared.gate.weight, lp["shared_gate"].T),
                (layer.shared.up.weight, lp["shared_up"].T),
                (layer.shared.down.weight, lp["shared_down"].T)):
            param.data()._rebind(jnp.asarray(value, jnp.float32))
        m = layer.ffn_norm(mx.np.array(onp.asarray(h)))
        with mx.autograd.train_mode():
            part = layer.experts(m)._data + layer.shared(m)._data
        total = total + (part - shared)
        rows += int(layer.experts.expert_load.data().asnumpy().sum())
    assert rows == 24 * CFG["num_experts_per_token"]     # every pick landed on some rank
    onp.testing.assert_allclose(total, want, rtol=2e-4, atol=2e-5)


def test_flash_path_in_interpret_mode_equals_the_dense_path():
    cfg = dict(CFG)
    x = mx.np.array(onp.random.default_rng(2).integers(0, 50, (1, 128)), dtype="int32")
    dense = build(use_flash=False)(x).asnumpy()
    flash = build(use_flash=True)(x).asnumpy()
    onp.testing.assert_allclose(flash, dense, rtol=2e-4, atol=2e-5)


def test_reference_in_blocks_equals_the_whole():
    model = build()
    params, x = reference_params(model), jnp.asarray(ids(4))
    whole = ref.loss_and_grads(params, x, REF_CFG, experts_held=4, ep_rank=1)
    blocks = ref.loss_and_grads(params, x, REF_CFG, experts_held=4, ep_rank=1, block=8)
    for a, b in zip(jax.tree_util.tree_leaves(whole), jax.tree_util.tree_leaves(blocks)):
        onp.testing.assert_allclose(b, a, rtol=1e-4, atol=1e-6)


@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
def test_fused_step_trains_every_kind_of_layer(remat):
    model = build(remat=remat)
    before = model.layer1.experts.correction_bias.data().asnumpy().copy()
    trainer = mx.gluon.Trainer(model.collect_params(), "adamw", {"learning_rate": 1e-2})
    step = mx.gluon.FusedTrainStep(CausalLMLoss(model), trainer)
    x = mx.np.array(ids(7), dtype="int32")
    losses = [float(step(x, batch_size=B).asnumpy()) for _ in range(4)]
    assert losses[-1] < losses[0]
    assert onp.array_equal(model.layer1.experts.correction_bias.data().asnumpy(), before)
    loads = [l for l in moe.expert_loads() if any(l["rows"])][-4:]
    assert all(l["first_expert"] == 4 and len(l["rows"]) == 4 for l in loads)


def test_partition_rules_name_every_parameter_of_the_new_blocks():
    import re

    from mxnet_tpu.models.decoder import KimiDeltaAttention, LatentAttention, SwiGLU
    model = build()
    for block, cls in ((model.layer0.attend.attention, KimiDeltaAttention),
                       (model.layer3.attend.attention, LatentAttention),
                       (model.layer0.mlp, SwiGLU), (model.layer1.experts, RoutedExperts)):
        rules = cls.partition_rules()
        for name in block.collect_params():
            assert sum(bool(re.match(pattern, name)) for pattern, _spec in rules) == 1, name


def test_kda_initial_decays_follow_the_stated_convention():
    model = build()
    att = model.layer0.attend.attention
    a = onp.exp(att.A_log.data().asnumpy())
    dt = onp.log1p(onp.exp(att.dt_bias.data().asnumpy()))          # softplus
    assert (a >= 1.0).all() and (a <= 16.0).all()
    assert (dt >= 1e-3 * 0.999).all() and (dt <= 1e-1 * 1.001).all()
    assert math.isclose(float(att.o_norm.data().asnumpy().mean()), 1.0)
    assert not att.g_b.bias.data().asnumpy().any()
