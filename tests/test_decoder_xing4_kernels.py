"""mHC's stream passes as Mosaic kernels (`ops/hyper_connection.py`: the
pre-mix `mhc_pre_fwd` / `mhc_pre_bwd` and the combine `mhc_post_fwd` /
`mhc_post_bwd`), interpreted on the CPU, against the XLA form that every other
platform takes and that they replace on the TPU (`models.decoder`): n = 4
streams of U = 256, in f32 and bf16, over token counts that are and are not a
multiple of the kernels' tile.

Tolerances: both forms compute the same f32 mathematics between the same
loads and stores, in another order (the row's sum of squares in chunks, the
projection's cotangent against W and X as three bf16 parts, the gradients'
terms summed in f32 before one store where autodiff stores each in the
streams' dtype first): f32 values agree to a few roundings (2e-5 of the
largest entry), bf16 values to a rounding of the stored dtype (2^-8 relative)
and bf16 gradients to 1% of the largest entry."""
import jax
import jax.numpy as jnp
import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu.models.decoder import HyperConnection, _hc_combine, _stream_sum, sinkhorn
from mxnet_tpu.ops import hyper_connection as hc
from mxnet_tpu.ops.invoke import invoke

N, UNITS, EPS = 4, 256, 1e-6
WIDTH = 2 * N + N * N
F32, BF16 = jnp.float32, jnp.bfloat16
# 100 tokens are padded to one tile of 112; 300 to three of 128, over which
# W's gradient is summed
TOKENS = [pytest.param((2, 50), id="100-tokens"), pytest.param((1, 300), id="300-tokens")]
DTYPES = [pytest.param(F32, id="f32"), pytest.param(BF16, id="bf16")]


def post_and_res(z, alpha, bias, lo=-30.0, hi=30.0):
    """H_post and H_res from the logits, as `HyperConnection` makes them."""
    post = 2.0 * jax.nn.sigmoid(alpha[1] * z[..., N:2 * N] + bias[N:2 * N])
    res = sinkhorn(jnp.clip(alpha[2] * z[..., 2 * N:] + bias[2 * N:], lo, hi)
                   .reshape(z.shape[:-1] + (N, N)), 20, 1e-6)
    return post, res


def projection(gamma, phi):
    return (gamma.astype(F32)[:, None] * phi.astype(F32)).astype(phi.dtype)


def xla_logits(x, w):
    flat = x.reshape(x.shape[:2] + (-1,))
    xf = flat.astype(F32)
    scale = jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + EPS)
    return jnp.dot(flat, w, preferred_element_type=F32) * scale


def xla_mixes(x, w, alpha, bias):
    """`HyperConnection`'s XLA form: (u, H_post, H_res, z)."""
    z = xla_logits(x, w)
    pre = jax.nn.sigmoid(alpha[0] * z[..., :N] + bias[:N])
    return (_stream_sum(x, pre).astype(x.dtype), *post_and_res(z, alpha, bias), z)


def inputs(dtype, shape, seed=0, past_the_clamp=False):
    """x, the sublayer's weight, gamma, phi, alpha, bias, and a cotangent of
    the new streams; the mixes drawn away from uniform."""
    rng = onp.random.default_rng(seed)
    b, t = shape
    alpha = rng.uniform(0.5, 1.5, 3)
    bias = rng.normal(size=WIDTH)
    if past_the_clamp:    # a permutation's logits at 1e4: clamped to +-30
        alpha[2] = 0.0
        bias[2 * N:] = 1e4 * (2.0 * onp.eye(N)[[2, 0, 3, 1]] - 1.0).ravel()
    return (jnp.asarray(rng.normal(size=(b, t, N, UNITS)), dtype),
            jnp.asarray(rng.normal(size=(UNITS, UNITS)) / 16, F32),
            jnp.asarray(rng.uniform(0.5, 1.5, N * UNITS), dtype),
            jnp.asarray(rng.normal(size=(N * UNITS, WIDTH)) * 0.2, dtype),
            jnp.asarray(alpha, F32), jnp.asarray(bias, F32),
            jnp.asarray(rng.normal(size=(b, t, N, UNITS)), dtype))


def close(got, want, dtype, name="", grad=False):
    got, want = onp.asarray(got, onp.float32), onp.asarray(want, onp.float32)
    assert onp.isfinite(got).all(), name
    scale = float(onp.abs(want).max())
    tol = 2e-5 if dtype == F32 else (1e-2 if grad else 2.0 ** -8)
    onp.testing.assert_allclose(got, want, rtol=tol, atol=tol * scale, err_msg=name)


# ---------------------------------------------------------------------------
# each kernel's forward against the XLA form
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("shape", TOKENS)
@pytest.mark.parametrize("dtype", DTYPES)
def test_the_pre_mix_kernel_gives_the_xla_forms_u_and_logits(dtype, shape):
    x, _wy, gamma, phi, alpha, bias, _ct = inputs(dtype, shape)
    w = projection(gamma, phi)
    want_u, _post, _res, want_z = xla_mixes(x, w, alpha, bias)
    tokens = x.shape[0] * x.shape[1]
    ab = jnp.stack([jnp.full((N,), alpha[0]), bias[:N]])
    flat = hc._padded(x.reshape(tokens, -1), tokens)
    u, z = hc._mhc_pre_fwd(flat, w.T, ab, N, EPS, True)
    padded = -(-tokens // 16) * 16 if tokens < 128 else -(-tokens // 128) * 128
    assert u.dtype == dtype and z.dtype == F32 and u.shape[0] == z.shape[0] == padded
    close(z[:tokens], want_z.reshape(tokens, -1), F32, "z")
    close(u[:tokens], want_u.reshape(tokens, -1), dtype, "u")
    # the padded rows are zero in and zero out
    assert not onp.asarray(u[tokens:], onp.float32).any()


@pytest.mark.parametrize("shape", TOKENS)
@pytest.mark.parametrize("dtype", DTYPES)
def test_the_mixes_and_the_combine_give_the_xla_forms(monkeypatch, dtype, shape):
    x, wy, gamma, phi, alpha, bias, _ct = inputs(dtype, shape, seed=1)
    w = projection(gamma, phi)
    want = xla_mixes(x, w, alpha, bias)
    got = hc.mixes(x, w, alpha, bias, post_and_res, EPS)
    for name, g, v in zip(("u", "post", "res"), got, want):
        close(g, v, dtype if name == "u" else F32, name)
    assert got[3] is x or onp.array_equal(got[3], x)        # the streams, for the fold
    y = jnp.tanh(want[0].astype(F32) @ wy).astype(dtype)
    xla = _hc_combine(x, y, want[1], want[2])
    monkeypatch.setattr(hc, "takes_kernels", lambda units: True)
    for out in (hc.combine(x, y, want[1], want[2]), _hc_combine(x, y, want[1], want[2])):
        assert out.dtype == dtype and out.shape == x.shape
        close(out, xla, dtype, "X'")


# ---------------------------------------------------------------------------
# a sublayer between them: the gradients of x, y, gamma, phi, alpha and bias
# ---------------------------------------------------------------------------
def sublayer_step(kernels, folded):
    """X' of a tanh sublayer between the mixes and the combine: the XLA form,
    the kernels with the fold (`HyperConnection.around`'s) or the kernels'
    combine given X itself (`_hc_combine`'s)."""
    def f(x, wy, gamma, phi, alpha, bias):
        w = projection(gamma, phi)
        if kernels:
            u, post, res, streams = hc.mixes(x, w, alpha, bias, post_and_res, EPS)
        else:
            u, post, res, _z = xla_mixes(x, w, alpha, bias)
        y = jnp.tanh(u.astype(F32) @ wy).astype(x.dtype)
        if not kernels:
            return _hc_combine(x, y, post, res)
        return hc.combine(streams, y, post, res, folded=True) if folded \
            else hc.combine(x, y, post, res)
    return f


@pytest.mark.parametrize("folded", [True, False], ids=["folded", "honest-combine"])
@pytest.mark.parametrize("shape", TOKENS)
@pytest.mark.parametrize("dtype", DTYPES)
def test_the_kernel_pairs_vjp_is_the_xla_forms(dtype, shape, folded):
    *args, ct = inputs(dtype, shape, seed=2)
    want_out, want_vjp = jax.vjp(sublayer_step(False, False), *args)
    got_out, got_vjp = jax.vjp(sublayer_step(True, folded), *args)
    close(got_out, want_out, dtype, "X'")
    for name, g, w in zip("x y gamma phi alpha bias".split(), got_vjp(ct), want_vjp(ct)):
        assert g.dtype == w.dtype and g.shape == w.shape, name
        close(g, w, dtype, name, grad=True)


@pytest.mark.parametrize("dtype", DTYPES)
def test_logits_past_the_clamp_give_the_xla_forms_mixes_and_gradients(dtype):
    """H_res's logits at a permutation of +-1e4 are clamped in both forms: the
    mix is that permutation, and the gradients (none through the clamp) agree."""
    *args, ct = inputs(dtype, (1, 40), seed=3, past_the_clamp=True)
    x, _wy, gamma, phi, alpha, bias = args
    _u, _post, res, _streams = hc.mixes(x, projection(gamma, phi), alpha, bias,
                                        post_and_res, EPS)
    onp.testing.assert_allclose(res, onp.broadcast_to(onp.eye(N)[[2, 0, 3, 1]], res.shape),
                                atol=1e-5)
    want_out, want_vjp = jax.vjp(sublayer_step(False, False), *args)
    got_out, got_vjp = jax.vjp(sublayer_step(True, True), *args)
    close(got_out, want_out, dtype, "X'")
    for name, g, w in zip("x y gamma phi alpha bias".split(), got_vjp(ct), want_vjp(ct)):
        close(g, w, dtype, name, grad=True)


# ---------------------------------------------------------------------------
# the dispatch and its counter
# ---------------------------------------------------------------------------
def test_the_kernels_are_taken_on_tpu_where_a_stream_tiles(monkeypatch):
    assert not hc.takes_kernels(3584)                # this process computes on the CPU
    monkeypatch.setattr(hc._context, "on_tpu", lambda: True)
    assert hc.takes_kernels(3584) and hc.takes_kernels(128)
    assert not hc.takes_kernels(64) and not hc.takes_kernels(3600)


@pytest.mark.parametrize("kernels", [False, True], ids=["xla", "pallas"])
def test_each_trace_of_the_mixes_counts_the_path_it_took(monkeypatch, kernels):
    from mxnet_tpu import telemetry
    monkeypatch.setattr(hc, "takes_kernels", lambda units: kernels)

    def count(path):
        return telemetry.default_registry().get_sample_value(
            "mxtpu_hyperconnection_lowerings", {"path": path}) or 0.0
    paths = ("xla", "pallas")
    before = [count(p) for p in paths]
    block = HyperConnection(UNITS, N)
    block.initialize()
    x = inputs(F32, (1, 16))[0]
    u, post, res = block(mx.np.array(x))
    assert u.shape == (1, 16, UNITS) and post.shape == (1, 16, N) and res.shape == (1, 16, N, N)
    assert [count(p) - b for p, b in zip(paths, before)] == [1.0 - kernels, 1.0 * kernels]


def test_around_on_the_eager_tape_gives_the_xla_forms_gradients(monkeypatch):
    """`HyperConnection.around` recorded op by op (the fold spans two tape
    nodes: the combine hands the streams dX' and the pre-mix mixes it)."""
    x, wy, gamma, phi, alpha, bias, ct = inputs(F32, (2, 20), seed=4)
    grads = {}
    for kernels in (False, True):
        monkeypatch.setattr(hc, "takes_kernels", lambda units: kernels)
        block = HyperConnection(UNITS, N)
        block.initialize()
        for param, value in ((block.gamma, gamma), (block.phi, phi), (block.alpha, alpha),
                             (block.bias, bias)):
            param.data()._rebind(value)
        xs, w_y = mx.np.array(x), mx.np.array(wy)
        xs.attach_grad()
        w_y.attach_grad()
        with mx.autograd.record():
            out = block.around(xs, lambda u: invoke(
                lambda u, w: jnp.tanh(u @ w), (u, w_y), name="sublayer"))
            loss = invoke(lambda o, c: jnp.sum(o * c), (out, mx.np.array(ct)), name="loss")
        loss.backward()
        grads[kernels] = [xs.grad.asnumpy(), w_y.grad.asnumpy()] + [
            p.grad().asnumpy() for p in (block.gamma, block.phi, block.alpha, block.bias)]
    for name, g, w in zip("x y gamma phi alpha bias".split(), grads[True], grads[False]):
        close(g, w, F32, name, grad=True)


# ---------------------------------------------------------------------------
# a decoder's training step on either path
# ---------------------------------------------------------------------------
def test_a_decoder_step_with_remat_trains_alike_on_both_paths(monkeypatch):
    """The Xing blocks at U = 128 (a dense layer and the MTP module's, each
    recomputed whole under ``remat``) through `FusedTrainStep`: the losses of
    three SGD steps and the parameters after them agree with the kernels
    interpreted and with the XLA form."""
    import os

    from chipbench import run as bench
    from mxnet_tpu.models import CausalLMLoss, DecoderLM
    cfgmod = bench.load_py(os.path.join(bench.HERE, "configs", "xing4_29b_a4b.py"))
    cfg = dict(bench.load_json(bench.HERE, "configs", "xing4_29b_a4b.json"),
               hidden_size=128, intermediate_size=192, kv_lora_rank=16, q_lora_rank=24,
               qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16, num_attention_heads=2,
               vocab_size=61, dtype="float32", remat=True, layers_held=[0])
    ids = mx.np.array(onp.random.default_rng(5).integers(0, 61, (2, 24)), dtype="int32")
    runs = {}
    for kernels in (False, True):
        monkeypatch.setattr(hc, "takes_kernels", lambda units: kernels)
        mx.random.seed(6)
        model = DecoderLM(**cfgmod.model_arguments(cfg))
        model.initialize()
        rng = onp.random.default_rng(8)
        for name, param in model.collect_params().items():
            if name.endswith("hc.alpha"):
                param.data()._rebind(jnp.asarray(rng.uniform(0.5, 1.5, 3), F32))
            elif name.endswith("hc.bias"):
                param.data()._rebind(jnp.asarray(rng.normal(size=WIDTH), F32))
        trainer = mx.gluon.Trainer(model.collect_params(), "sgd", {"learning_rate": 0.05})
        step = mx.gluon.FusedTrainStep(CausalLMLoss(model), trainer)
        losses = [float(step(ids, batch_size=2).asnumpy()) for _ in range(3)]
        runs[kernels] = losses, {k: v.data().asnumpy() for k, v in
                                 model.collect_params().items()}
    assert runs[True][0][-1] < runs[True][0][0]
    onp.testing.assert_allclose(runs[True][0], runs[False][0], rtol=1e-5)
    for name, value in runs[False][1].items():
        onp.testing.assert_allclose(runs[True][1][name], value, rtol=1e-4, atol=1e-6,
                                    err_msg=name)


def test_the_kernel_path_reader_reads_the_counter(monkeypatch):
    """`mhc_kernel_path_pct.tok` over a registry of its own: nothing where
    no counter or no trace is there, 100 where every trace took the kernels,
    less where one fell back."""
    import os

    from chipbench import run as bench
    from mxnet_tpu import telemetry
    from mxnet_tpu.telemetry import registry as published
    reader = bench.load_py(os.path.join(bench.HERE, "layer_metrics", "mhc_kernel_path_pct.tok.py"))
    registry = telemetry.MetricsRegistry()
    monkeypatch.setattr(published, "_default", registry)
    assert reader.read(None, [], {}) is None
    monkeypatch.setattr(hc, "takes_kernels", lambda units: True)
    block = HyperConnection(UNITS, N)
    block.initialize()
    jax.eval_shape(lambda x: block(mx.np.array(x))[0]._data, inputs(F32, (1, 16))[0])
    assert reader.read(None, [], {}) == 100.0
    registry.counter("mxtpu_hyperconnection_lowerings", "", labelnames=("path",)) \
        .labels(path="xla").inc()
    assert reader.read(None, [], {}) == 50.0
