"""Xing4.0's block set (mHC's residual streams, q-latent MLA with YaRN-rotated
shared channels, the sigmoid top-k router beside a shared expert, the
multi-token prediction module and its loss term) against the plain f32
reference (models/reference/xing4.py) on seeded weights, at tiny widths on
the CPU: U = 64, 4 streams, 4 heads, 8 experts of which 2 are held, a
vocabulary of 97, T = 16, f32; the published layers 0 (dense) and 2-3
(routed) and the MTP module.

Tolerances: the system and the reference compute the same f32 mathematics in
another order (the mixes' norm scale after the projection, the rotary table
in Python doubles, the experts sorted and gathered), so forward values agree
to a few f32 roundings (rtol 2e-5 of values near 1) and gradients, summed
over two heads' tokens and 20 Sinkhorn steps, to 3e-3 of the largest entry."""
import math
import os
import re

import jax
import jax.numpy as jnp
import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu.models import CausalLMLoss, DecoderLM
from mxnet_tpu.models.decoder import (DecoderLayer, HyperConnection, LatentAttention,
                                      MultiTokenPrediction, SwiGLU, mtp_losses, rope_inv_freq,
                                      sinkhorn)
from mxnet_tpu.models.reference import kimi_linear as kimi_ref
from mxnet_tpu.models.reference import xing4 as ref
from mxnet_tpu.parallel import RoutedExperts

from chipbench import run as bench

CFGMOD = bench.load_py(os.path.join(bench.HERE, "configs", "xing4_29b_a4b.py"))
PUBLISHED = bench.load_json(bench.HERE, "configs", "xing4_29b_a4b.json")
CFG = dict(PUBLISHED, hidden_size=64, intermediate_size=96, moe_intermediate_size=32,
           kv_lora_rank=12, q_lora_rank=24, qk_nope_head_dim=16, qk_rope_head_dim=8,
           v_head_dim=16, num_attention_heads=4, n_routed_experts=2, router_experts=8,
           ep_rank=1, num_experts_per_tok=2, vocab_size=97, dtype="float32", remat=False,
           layers_held=[0, 2, 3])
B, T = 2, 16
REF_CFG = CFGMOD.reference_config(CFG)


def build(held=2, rank=1, seed=3, **kw):
    """The tiny model, its mixes and correction biases drawn away from their
    initial values (uniform mixes and a zero bias would hide a wrong index)."""
    cfg = dict(CFG, n_routed_experts=held, ep_rank=rank)
    mx.random.seed(seed)
    model = DecoderLM(**dict(CFGMOD.model_arguments(cfg), **kw))
    model.initialize()
    rng = onp.random.default_rng(8)
    for name, param in model.collect_params().items():
        if name.endswith("correction_bias"):
            param.data()._rebind(jnp.asarray(rng.normal(size=param.shape) * 0.05, jnp.float32))
        elif name.endswith(("hc.alpha", "ffn_hc.alpha")):
            param.data()._rebind(jnp.asarray(rng.uniform(0.5, 1.5, param.shape), jnp.float32))
        elif name.endswith(("hc.bias", "ffn_hc.bias")):
            param.data()._rebind(jnp.asarray(rng.normal(size=param.shape), jnp.float32))
    return model


def reference_params(model):
    p = {k: jnp.asarray(v.data()._data, jnp.float32) for k, v in model.collect_params().items()}
    return CFGMOD.reference_params(p, CFG)


def ids(seed=0):
    return onp.random.default_rng(seed).integers(0, CFG["vocab_size"], (B, T))


SHARES = [pytest.param(8, 0, id="all-8-experts"), pytest.param(2, 1, id="experts-2-and-3"),
          pytest.param(4, 1, id="experts-4-to-7")]


def test_the_cut_builds_the_published_kinds_and_the_mtp_module():
    model = build()
    assert model.layer_kinds == [("latent_attention", "dense"), ("latent_attention", "sparse"),
                                 ("latent_attention", "sparse")]
    assert ref.layer_kinds(REF_CFG) == ["dense", "sparse", "sparse"]
    assert hasattr(model.layer0, "mlp") and hasattr(model.layer1, "shared")
    assert isinstance(model.mtp, MultiTokenPrediction) and model.mtp_weight == 0.1
    assert hasattr(model.mtp.layer, "experts") and model.mtp.layer.experts.first_expert == 2
    assert model.layer2.experts.first_expert == 2 and model.layer2.experts._top_k == 2
    for layer in (model.layer0, model.mtp.layer):
        assert isinstance(layer.attend.hc, HyperConnection)
        assert isinstance(layer.ffn_hc, HyperConnection)
        assert layer.attend.hc.phi.shape == (4 * 64, 24)


@pytest.mark.parametrize("held,rank", SHARES)
def test_both_heads_and_both_losses_match_the_reference(held, rank):
    model = build(held, rank)
    x = ids()
    main, ahead = (o.asnumpy() for o in model(mx.np.array(x, dtype="int32")))
    params = reference_params(model)
    share = {"experts_held": held, "ep_rank": rank}
    want_main, want_ahead = ref.logits(params, jnp.asarray(x), REF_CFG, **share)
    onp.testing.assert_allclose(main, want_main, rtol=2e-5, atol=2e-6)
    # the MTP head at every position but the last, whose next token is not there
    assert ahead.shape == (B, T, 97) and want_ahead.shape == (B, T - 1, 97)
    onp.testing.assert_allclose(ahead[:, :-1], want_ahead, rtol=2e-5, atol=2e-6)
    loss = float(CausalLMLoss(model)(mx.np.array(x, dtype="int32")).asnumpy())
    ce_main, ce_mtp = ref.losses(params, jnp.asarray(x), REF_CFG, **share)
    assert loss == pytest.approx(float(ce_main) + 0.1 * float(ce_mtp), rel=1e-5)
    assert float(ce_mtp) > 3.0                     # a term of its own, not a copy of zero


def test_the_mtp_term_is_zero_when_its_weight_is():
    x = mx.np.array(ids(2), dtype="int32")
    with_term = float(CausalLMLoss(build())(x).asnumpy())
    without = build(mtp=dict(weight=0.0))
    loss = float(CausalLMLoss(without)(x).asnumpy())
    params = reference_params(without)
    ce_main, ce_mtp = ref.losses(params, jnp.asarray(ids(2)), REF_CFG, experts_held=2,
                                 ep_rank=1)
    assert loss == pytest.approx(float(ce_main), rel=1e-6)
    assert with_term == pytest.approx(float(ce_main) + 0.1 * float(ce_mtp), rel=1e-5)
    zero = dict(REF_CFG, mtp_loss_weight=0.0)
    assert float(ref.loss(params, jnp.asarray(ids(2)), zero, experts_held=2, ep_rank=1)) \
        == pytest.approx(float(ce_main), rel=1e-6)


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
        model = build()
        x = ids(1)
        loss, got = _system_grads(model, x)
        want_loss, want = ref.loss_and_grads(reference_params(model), jnp.asarray(x), REF_CFG,
                                             experts_held=2, ep_rank=1)

        # the reference's gradients under the system's names: the same mapping,
        # applied to a tree of names, walked back
        class _Name(str):
            T = property(lambda self: _Name(self + "^T"))

        theirs = {}
        index = CFGMOD.reference_params({k: _Name(k) for k in model.collect_params()}, CFG)
        for path, name in jax.tree_util.tree_leaves_with_path(index):
            g = want
            for step in path:
                g = g[step.key if hasattr(step, "key") else step.idx]
            theirs[name.removesuffix("^T")] = g.T if name.endswith("^T") else g
        _GRAD_CACHE.update(loss=loss, got=got, want_loss=float(want_loss), want=theirs,
                           names=sorted(model.collect_params()))
    return _GRAD_CACHE


MLA = "q_a.weight q_norm.gamma q_b.weight kv_a.weight kv_norm.gamma kv_b.weight o_proj.weight"
HC = "gamma phi alpha bias"
SPARSE = ("experts.router experts.gate experts.up experts.down shared.gate.weight "
          "shared.up.weight shared.down.weight")


def _layer_names(prefix, dense):
    return [prefix + n for n in (
        ["attend.norm.gamma", "ffn_norm.gamma"]
        + ["attend.attention." + p for p in MLA.split()]
        + ["attend.hc." + p for p in HC.split()] + ["ffn_hc." + p for p in HC.split()]
        + (["mlp.gate.weight", "mlp.up.weight", "mlp.down.weight"] if dense
           else SPARSE.split()))]


PARAM_NAMES = ["embed.weight", "norm.gamma", "head.weight", "mtp.hnorm.gamma",
               "mtp.enorm.gamma", "mtp.eh_proj.weight", "mtp.norm.gamma"] \
    + _layer_names("layer0.", True) + _layer_names("layer1.", False) \
    + _layer_names("layer2.", False) + _layer_names("mtp.layer.", False)


@pytest.mark.parametrize("name", PARAM_NAMES)
def test_gradient_of_every_parameter_matches_the_reference(name):
    both = _grads_both()
    assert abs(both["loss"] - both["want_loss"]) < 1e-5
    want = onp.asarray(both["want"][name])
    scale = onp.abs(want).max()
    assert scale > 0, "a parameter without a gradient tests nothing"
    onp.testing.assert_allclose(both["got"][name], want, rtol=3e-3, atol=3e-3 * scale)


def test_grad_names_cover_every_trainable_parameter_and_not_the_state():
    got = _grads_both()["got"]
    assert sorted(PARAM_NAMES) == sorted(got)
    frozen = set(_grads_both()["names"]) - set(got)
    assert frozen and all(n.endswith(("correction_bias", "expert_load", "mtp_loss"))
                          for n in frozen)


# ---------------------------------------------------------------------------
# the residual path
# ---------------------------------------------------------------------------
def _permutation_logits(scale):
    return scale * (2.0 * jnp.eye(4)[jnp.asarray([2, 0, 3, 1])] - 1.0)


SINKHORN_CASES = {
    "normal": lambda: jax.random.normal(jax.random.key(0), (64, 4, 4)),
    "all-at-plus-30": lambda: jnp.full((3, 4, 4), 30.0),
    "all-at-minus-30": lambda: jnp.full((3, 4, 4), -30.0),
    "rows-at-plus-and-minus-30": lambda: jnp.asarray([30.0, -30.0, 30.0, -30.0])[:, None]
    * jnp.ones((1, 4, 4)),
    "a-permutation-at-plus-minus-30": lambda: _permutation_logits(30.0)[None],
    "beyond-the-clamp": lambda: _permutation_logits(1e4)[None],
}


@pytest.mark.parametrize("case", sorted(SINKHORN_CASES))
def test_sinkhorn_makes_every_row_and_column_sum_to_one(case):
    """20 iterations on the clamped logits, as a layer makes them: the sums
    are within 1e-5 of one (each later denominator carries hc_eps = 1e-6),
    finite beyond the clamp, and the reference's own form agrees."""
    logits = jnp.clip(SINKHORN_CASES[case](), -30.0, 30.0)
    m = sinkhorn(logits, 20, 1e-6)
    assert onp.isfinite(onp.asarray(m)).all() and float(m.min()) >= 0.0
    onp.testing.assert_allclose(jnp.sum(m, axis=-1), 1.0, atol=1e-5)
    onp.testing.assert_allclose(jnp.sum(m, axis=-2), 1.0, atol=1e-5)
    onp.testing.assert_allclose(m, ref.sinkhorn_knopp(logits, 20, 1e-6), rtol=1e-5, atol=1e-7)


def test_the_mixes_stay_finite_beyond_the_clamp():
    """A layer's residual logits far past 30 are clamped: with a permutation
    pattern (the bias) the mix is that permutation, rows and columns within
    1e-5 of one; with a random pattern (alpha_res = 1e6) it stays finite and
    its columns sum to one (Sinkhorn ends on the columns; 20 steps need not
    bring the rows of a pattern with no permutation inside it to one)."""
    hc = HyperConnection(8, 4)
    hc.initialize()
    x = mx.np.array(onp.random.default_rng(1).normal(size=(1, 5, 4, 8)))
    hc.alpha.data()._rebind(jnp.asarray([1.0, 1.0, 0.0], jnp.float32))
    hc.bias.data()._rebind(jnp.concatenate(
        [jnp.zeros(8), _permutation_logits(1e4).ravel()]).astype(jnp.float32))
    u, post, res = (o.asnumpy() for o in hc(x))
    assert onp.isfinite(u).all() and ((post > 0) & (post < 2)).all()
    onp.testing.assert_allclose(res, onp.broadcast_to(
        onp.eye(4)[[2, 0, 3, 1]], res.shape), atol=1e-5)
    hc.alpha.data()._rebind(jnp.asarray([1.0, 1.0, 1e6], jnp.float32))
    hc.bias.data()._rebind(jnp.zeros((24,), jnp.float32))
    res = hc(x)[2].asnumpy()
    assert onp.isfinite(res).all() and (res >= 0).all()
    onp.testing.assert_allclose(res.sum(-2), 1.0, atol=1e-5)


def test_one_stream_with_unit_mixes_is_the_plain_layer(monkeypatch):
    """n = 1, H_pre = H_post = 1 and H_res = I forced: X <- X + F(X), the
    plain `DecoderLayer` with the same attention, norms and FFN.  (Sinkhorn
    itself would give 1 / (1 + 1e-6)^39 for a 1 x 1 matrix: hc_eps.)"""
    from mxnet_tpu.models import decoder
    monkeypatch.setattr(decoder, "sinkhorn", lambda logits, iters, eps: jnp.ones_like(logits))
    def layer(hc_mult):
        mx.random.seed(5)
        att = LatentAttention(16, 2, 8, 4, 8, 6, q_lora_rank=10, epsilon=1e-6,
                              rope=CFGMOD.rope(CFG), softmax_scale=0.3)
        out = DecoderLayer(16, att, mlp=SwiGLU(16, 24), hc_mult=hc_mult)
        out.initialize()
        return out

    plain, mixed = layer(None), layer(1)
    for name, param in plain.collect_params().items():
        mixed.collect_params()[name].data()._rebind(param.data()._data)
    for hc in (mixed.attend.hc, mixed.ffn_hc):
        # sigmoid(b_pre) = 1 and 2 sigmoid(b_post) = 1 in f32; a 1 x 1 Sinkhorn is 1
        hc.alpha.data()._rebind(jnp.zeros((3,), jnp.float32))
        hc.bias.data()._rebind(jnp.asarray([40.0, 0.0, 0.0], jnp.float32))
    x = jnp.asarray(onp.random.default_rng(6).normal(size=(2, 8, 16)), jnp.float32)
    want = plain(mx.np.array(x)).asnumpy()
    got = mixed(mx.np.array(x[:, :, None, :])).asnumpy()
    assert got.shape == (2, 8, 1, 16)
    onp.testing.assert_allclose(got[:, :, 0], want, rtol=1e-6, atol=1e-6)


def test_the_ranks_routed_parts_and_the_shared_expert_once_add_up_to_the_uncut_layer():
    """8 experts over 4 ranks of 2: what each rank's FFN gives for its own
    experts, minus the shared expert that every rank computes alike, summed
    over the ranks, plus that shared expert once, is the uncut reference's FFN."""
    rng = onp.random.default_rng(5)
    u = jnp.asarray(rng.normal(size=(1, 24, 64)), jnp.float32)
    whole = ref.init_params(jax.random.key(4), REF_CFG, std=0.3)
    lp = dict(whole["layers"][1], bias=jnp.asarray(rng.normal(size=(8,)) * 0.05, jnp.float32))
    with jax.default_matmul_precision("highest"):
        want = ref.ffn(u, lp, REF_CFG, 8, 0)
        shared = ref.swiglu(ref.rms_norm(u, lp["norm2"], 1e-6), lp["shared_gate"],
                            lp["shared_up"], lp["shared_down"])
    total, rows = shared, 0
    for rank in range(4):
        model = build(2, rank, seed=0)
        layer, held = model.layer1, slice(2 * rank, 2 * rank + 2)
        for param, value in (
                (layer.ffn_norm.gamma, lp["norm2"]), (layer.experts.router, lp["router"]),
                (layer.experts.correction_bias, lp["bias"]),
                (layer.experts.gate, lp["gate"][held]), (layer.experts.up, lp["up"][held]),
                (layer.experts.down, lp["down"][held]),
                (layer.shared.gate.weight, lp["shared_gate"].T),
                (layer.shared.up.weight, lp["shared_up"].T),
                (layer.shared.down.weight, lp["shared_down"].T)):
            param.data()._rebind(jnp.asarray(value, jnp.float32))
        m = layer.ffn_norm(mx.np.array(onp.asarray(u)))
        with mx.autograd.train_mode():
            part = layer.experts(m)._data + layer.shared(m)._data
        total = total + (part - shared)
        rows += int(layer.experts.expert_load.data().asnumpy().sum())
    assert rows == 24 * CFG["num_experts_per_tok"]     # every pick landed on some rank
    onp.testing.assert_allclose(total, want, rtol=2e-4, atol=2e-5)


# ---------------------------------------------------------------------------
# latent attention: the q latent, the rotary channels, the scale
# ---------------------------------------------------------------------------
def test_latent_attention_without_q_latent_or_rope_is_the_nope_block():
    """Kimi-Linear's form: the parameter names it always had, and the NoPE
    reference's output."""
    mx.random.seed(2)
    att = LatentAttention(32, 2, 16, 8, 16, 12, epsilon=1e-5)
    att.initialize()
    assert sorted(att.collect_params()) == sorted(
        ["q_proj.weight", "kv_a.weight", "kv_norm.gamma", "kv_b.weight", "o_proj.weight"])
    p = {k: jnp.asarray(v.data()._data) for k, v in att.collect_params().items()}
    lp = {"norm1": jnp.ones((32,)), "wq": p["q_proj.weight"].T, "wkva": p["kv_a.weight"].T,
          "kv_norm": p["kv_norm.gamma"], "wkvb": p["kv_b.weight"].T, "wo": p["o_proj.weight"].T}
    cfg = {"num_attention_heads": 2, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
           "v_head_dim": 16, "kv_lora_rank": 12, "rms_norm_eps": 1e-5}
    h = jnp.asarray(onp.random.default_rng(3).normal(size=(2, 10, 32)), jnp.float32)
    x = kimi_ref.rms_norm(h, jnp.ones((32,)), 1e-5)
    with jax.default_matmul_precision("highest"):
        want = kimi_ref.mla(h, lp, cfg)
    onp.testing.assert_allclose(att(mx.np.array(x)).asnumpy(), want, rtol=2e-5, atol=2e-6)
    assert att._scale == (16 + 8) ** -0.5 and att._rope is None


def test_the_softmax_scale_and_the_rotary_table_are_deepseeks():
    scale = CFGMOD.softmax_scale(PUBLISHED)
    assert scale == pytest.approx(192 ** -0.5 * (0.1 * math.log(64) + 1) ** 2, rel=1e-12)
    assert round(scale, 6) == 0.144680
    assert ref.softmax_scale(PUBLISHED) == pytest.approx(scale, rel=1e-12)
    freq, factor = rope_inv_freq(64, CFGMOD.rope(PUBLISHED))
    assert factor == 1.0 and len(freq) == 32
    onp.testing.assert_allclose(ref.yarn_inv_freq(PUBLISHED), freq, rtol=1e-6)
    # the highest channels keep theta^(-2i/d), the lowest are divided by 64
    assert freq[0] == 1.0 and freq[31] == pytest.approx(10000 ** (-62 / 64) / 64, rel=1e-12)
    model = build()
    att = model.layer0.attend.attention
    assert att._scale == CFGMOD.softmax_scale(CFG) and att._rope[0] == rope_inv_freq(
        8, CFGMOD.rope(CFG))[0]


def test_latent_attention_with_q_latent_and_rope_matches_the_reference():
    mx.random.seed(9)
    att = LatentAttention(32, 2, 16, 8, 16, 12, q_lora_rank=20, epsilon=1e-6,
                          rope=CFGMOD.rope(CFG), softmax_scale=CFGMOD.softmax_scale(CFG))
    att.initialize()
    p = {k: jnp.asarray(v.data()._data) for k, v in att.collect_params().items()}
    lp = {"norm1": jnp.ones((32,)), "wqa": p["q_a.weight"].T, "q_norm": p["q_norm.gamma"],
          "wqb": p["q_b.weight"].T, "wkva": p["kv_a.weight"].T, "kv_norm": p["kv_norm.gamma"],
          "wkvb": p["kv_b.weight"].T, "wo": p["o_proj.weight"].T}
    cfg = dict(CFG, num_attention_heads=2, kv_lora_rank=12, q_lora_rank=20)
    h = jnp.asarray(onp.random.default_rng(4).normal(size=(2, 12, 32)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        want = ref.mla(h, lp, cfg)
        blocks = ref.mla_in_blocks(h, lp, cfg, 5)       # a last block past the end
    got = att(mx.np.array(ref.rms_norm(h, 1.0, 1e-6))).asnumpy()
    onp.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)
    onp.testing.assert_allclose(blocks, want, rtol=1e-5, atol=1e-6)


def test_rotary_scores_follow_the_relative_position_only():
    from mxnet_tpu.models.decoder import _rotate
    freq, factor = rope_inv_freq(64, CFGMOD.rope(PUBLISHED))
    rng = onp.random.default_rng(5)
    q, k = (jnp.asarray(rng.normal(size=(1, 12, 1, 64)), jnp.float32) for _ in range(2))
    pad = jnp.zeros((1, 5, 1, 64))

    def scores(q, k):
        return jnp.einsum("btd,bsd->bts", _rotate(q, freq, factor)[:, :, 0],
                          _rotate(k, freq, factor)[:, :, 0])
    later = scores(jnp.concatenate([pad, q], 1), jnp.concatenate([pad, k], 1))[:, 5:, 5:]
    onp.testing.assert_allclose(later, scores(q, k), rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# training through the normal path
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
def test_fused_step_trains_and_publishes_the_mtp_loss(remat):
    from mxnet_tpu import observe, telemetry
    model = build(remat=remat)
    trainer = mx.gluon.Trainer(model.collect_params(), "adamw", {"learning_rate": 1e-2})
    step = mx.gluon.FusedTrainStep(CausalLMLoss(model), trainer)
    x = mx.np.array(ids(7), dtype="int32")
    losses = [float(step(x, batch_size=B).asnumpy()) for _ in range(4)]
    assert losses[-1] < losses[0]
    # the MTP term of the last step, as auxiliary state the step wrote
    read = mtp_losses()
    name = model.mtp.mtp_loss.name
    assert 0.0 < read[name] < losses[-2] / 0.1
    assert telemetry.default_registry().get_sample_value(
        "mxtpu_mtp_loss", {"layer": name}) == read[name]
    assert any(e[5] == "mtp.loss" and e[6]["loss"] == read[name] for e in observe.events())
    # every trace of a mix and of the MTP module left its span
    spans = observe.spans()
    assert any(s["name"] == "mtp.trace" and s["args"]["tokens"] == B * T for s in spans)
    assert any(s["name"] == "mhc.trace" and s["args"]["tokens"] == B * T for s in spans)


def test_layers_of_one_kind_share_one_trace_and_train_as_if_traced_apart(monkeypatch):
    """Under ``remat`` the later routed layers and the MTP module's run
    through the first routed layer's trace (`DecoderLayer.run`): two traces
    of a layer for four layers, and the same losses, expert loads and
    parameters after three steps as with each layer traced on its own.  The
    step never makes Gluon's gradient buffers."""
    traced = []
    forward = DecoderLayer.forward
    monkeypatch.setattr(DecoderLayer, "forward",
                        lambda self, x: traced.append(self.name) or forward(self, x))
    runs = {}
    for shared in (False, True):
        model = build(remat=True)
        layers = [model.layer0, model.layer1, model.layer2, model.mtp.layer]
        assert [layer.like[0].name if layer.like else None for layer in layers] == \
            [None, None, "layer1", "layer1"]
        if not shared:
            for layer in layers:
                layer.like = ()
        trainer = mx.gluon.Trainer(model.collect_params(), "adamw", {"learning_rate": 1e-2})
        step = mx.gluon.FusedTrainStep(CausalLMLoss(model), trainer)
        x = mx.np.array(ids(7), dtype="int32")
        del traced[:]
        losses = [float(step(x, batch_size=B).asnumpy()) for _ in range(3)]
        params = {k: v.data().asnumpy() for k, v in model.collect_params().items()}
        assert all(v.grad()._buf is None for v in model.collect_params().values()
                   if v.grad_req != "null")
        runs[shared] = (sorted(traced), losses, params, layers)
    assert runs[False][0] == ["layer", "layer0", "layer1", "layer2"]
    assert runs[True][0] == ["layer0", "layer1"]
    assert runs[True][1] == runs[False][1]
    for name, value in runs[False][2].items():
        assert onp.array_equal(runs[True][2][name], value), name
    for apart, shared in zip(runs[False][3][1:], runs[True][3][1:]):
        assert shared.experts.picks == apart.experts.picks == B * T * CFG["num_experts_per_tok"]


def test_partition_rules_name_every_parameter_of_the_new_blocks():
    model = build()
    for block, cls in ((model.layer0.attend.attention, LatentAttention),
                       (model.layer0.attend.hc, HyperConnection),
                       (model.layer1.ffn_hc, HyperConnection),
                       (model.layer1.experts, RoutedExperts)):
        rules = cls.partition_rules()
        for name in block.collect_params():
            assert sum(bool(re.match(pattern, name)) for pattern, _spec in rules) == 1, name
