"""The decoder block set (models/decoder.py, parallel/moe.py's routed
experts) against the plain f32 reference (models/reference/mellum2.py) on
seeded weights, at tiny sizes on the CPU: one period of the layer pattern
(three window layers and a full one) with the window shorter than T."""
import math

import jax
import jax.numpy as jnp
import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu.models import CausalLMLoss, DecoderLM
from mxnet_tpu.models.decoder import rope_inv_freq
from mxnet_tpu.models.reference import mellum2 as ref
from mxnet_tpu.ops import grouped_matmul
from mxnet_tpu.parallel import moe

ROPE = {"full_attention": {"rope_type": "yarn", "rope_theta": 500000,
                           "factor": 16,
                           "original_max_position_embeddings": 8192,
                           "beta_fast": 32, "beta_slow": 1,
                           "attention_factor": 1.2772588722239782},
        "sliding_attention": {"rope_type": "default", "rope_theta": 500000}}
CFG = {"hidden_size": 32, "head_dim": 8, "num_attention_heads": 4,
       "num_key_value_heads": 2, "num_hidden_layers": 4,
       "layer_types": ["sliding_attention"] * 3 + ["full_attention"],
       "rms_norm_eps": 1e-6, "sliding_window": 6, "num_experts": 8,
       "num_experts_per_tok": 2, "moe_intermediate_size": 16,
       "vocab_size": 50, "rope_parameters": ROPE}
B, T = 2, 16


def build(experts_held=None, ep_rank=0, seed=3, **kw):
    mx.random.seed(seed)
    model = DecoderLM(
        CFG["vocab_size"], CFG["hidden_size"], CFG["layer_types"],
        CFG["num_attention_heads"], CFG["num_key_value_heads"],
        CFG["head_dim"], ROPE, CFG["sliding_window"],
        CFG["moe_intermediate_size"], CFG["num_experts"],
        CFG["num_experts_per_tok"], experts_held=experts_held,
        ep_rank=ep_rank, **kw)
    model.initialize()
    return model


def reference_params(model):
    """The system's parameters in the reference's layout (its matrices are
    (in, out); `nn.Dense` stores (out, in))."""
    p = {k: jnp.asarray(v.data()._data, jnp.float32)
         for k, v in model.collect_params().items()}
    layers = []
    for l in range(CFG["num_hidden_layers"]):
        a = f"layer{l}.attend.attention."
        e = f"layer{l}.experts."
        layers.append({
            "norm1": p[f"layer{l}.attend.norm.gamma"],
            "wq": p[a + "query.weight"].T, "wk": p[a + "key.weight"].T,
            "wv": p[a + "value.weight"].T, "wo": p[a + "proj.weight"].T,
            "norm2": p[f"layer{l}.ffn_norm.gamma"], "router": p[e + "router"],
            "gate": p[e + "gate"], "up": p[e + "up"], "down": p[e + "down"]})
    return {"embed": p["embed.weight"], "layers": layers,
            "norm": p["norm.gamma"], "head": p["head.weight"].T}


def reference_grad_of(name, grads):
    """The reference's gradient for the system parameter `name`."""
    if name == "embed.weight":
        return grads["embed"]
    if name == "norm.gamma":
        return grads["norm"]
    if name == "head.weight":
        return grads["head"].T
    layer, rest = name.split(".", 1)
    g = grads["layers"][int(layer[5:])]
    table = {"attend.norm.gamma": ("norm1", False), "ffn_norm.gamma": ("norm2", False),
             "attend.attention.query.weight": ("wq", True),
             "attend.attention.key.weight": ("wk", True),
             "attend.attention.value.weight": ("wv", True),
             "attend.attention.proj.weight": ("wo", True),
             "experts.router": ("router", False), "experts.gate": ("gate", False),
             "experts.up": ("up", False), "experts.down": ("down", False)}
    key, transposed = table[rest]
    return g[key].T if transposed else g[key]


def ids(seed=0):
    return onp.random.default_rng(seed).integers(0, CFG["vocab_size"], (B, T))


SHARES = [pytest.param(None, 0, id="all-8-experts"),
          pytest.param(4, 1, id="experts-4-to-7"),
          pytest.param(2, 0, id="experts-0-and-1")]


@pytest.mark.parametrize("held,rank", SHARES)
def test_logits_and_loss_match_the_reference(held, rank):
    model = build(held, rank)
    x = ids()
    logits = model(mx.np.array(x, dtype="int32")).asnumpy()
    share = {} if held is None else {"experts_held": held, "ep_rank": rank}
    params = reference_params(model)
    want = ref.logits(params, jnp.asarray(x), CFG, **share)
    onp.testing.assert_allclose(logits, want, rtol=2e-4, atol=2e-5)
    loss = CausalLMLoss(model)(mx.np.array(x, dtype="int32")).asnumpy()
    onp.testing.assert_allclose(
        loss, ref.loss(params, jnp.asarray(x), CFG, **share), rtol=1e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
def test_the_loss_picks_before_the_log_sum_exp_and_means_the_same(dtype):
    """`CausalLMLoss`'s value and gradient rule against the log-softmax written out: the same
    mean and the same softmax - onehot, in the logits' dtype, with no array of log-probabilities."""
    from mxnet_tpu.models import decoder
    logits = (3.0 * jax.random.normal(jax.random.key(0), (2, 7, 50))).astype(dtype)
    targets = jax.random.randint(jax.random.key(1), (2, 7), 0, 50)

    def written_out(logits):
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        return -jnp.take_along_axis(logp, targets[..., None], axis=-1).mean()
    want, want_grad = jax.value_and_grad(written_out)(logits)
    got, got_grad = jax.value_and_grad(decoder._next_token_nll)(logits, targets)
    onp.testing.assert_allclose(got, want, rtol=1e-6)
    assert got_grad.dtype == dtype
    onp.testing.assert_allclose(onp.asarray(got_grad, onp.float32), onp.asarray(want_grad, onp.float32),
                                rtol=1e-5 if dtype == jnp.float32 else 2.0 ** -7, atol=1e-8)
    assert "log_softmax" not in str(jax.make_jaxpr(jax.grad(decoder._next_token_nll))(logits, targets))


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
        want_loss, want = ref.loss_and_grads(
            reference_params(model), jnp.asarray(x), CFG, experts_held=4,
            ep_rank=1)
        _GRAD_CACHE.update(loss=loss, got=got, want_loss=float(want_loss),
                           want=want)
    return _GRAD_CACHE


PARAM_NAMES = ["embed.weight", "norm.gamma", "head.weight"] + [
    f"layer{l}.{rest}" for l in range(4) for rest in (
        "attend.norm.gamma", "attend.attention.query.weight",
        "attend.attention.key.weight", "attend.attention.value.weight",
        "attend.attention.proj.weight", "ffn_norm.gamma",
        "experts.router", "experts.gate", "experts.up", "experts.down")]


@pytest.mark.parametrize("name", PARAM_NAMES)
def test_gradient_of_every_parameter_matches_the_reference(name):
    both = _grads_both()
    assert abs(both["loss"] - both["want_loss"]) < 1e-5
    want = onp.asarray(reference_grad_of(name, both["want"]))
    scale = onp.abs(want).max()
    assert scale > 0, "a parameter without a gradient tests nothing"
    onp.testing.assert_allclose(both["got"][name], want, rtol=2e-3,
                                atol=2e-4 * scale)


def test_grad_names_cover_every_trainable_parameter():
    assert sorted(PARAM_NAMES) == sorted(_grads_both()["got"])


def test_the_four_shares_add_up_to_the_uncut_layer():
    """What each chip of an ep4 group computes for its own experts, summed
    over the four, is the reference's whole layer."""
    rng = onp.random.default_rng(5)
    n, u, f, e, k = 40, 32, 16, 8, 2
    m = jnp.asarray(rng.normal(size=(n, u)), jnp.float32)
    lp = {"norm2": jnp.ones((u,)),
          "router": jnp.asarray(rng.normal(size=(u, e)), jnp.float32),
          "gate": jnp.asarray(rng.normal(size=(e, u, f)) * 0.1, jnp.float32),
          "up": jnp.asarray(rng.normal(size=(e, u, f)) * 0.1, jnp.float32),
          "down": jnp.asarray(rng.normal(size=(e, f, u)) * 0.1, jnp.float32)}
    top_e, top_w = moe.route_top_k(m, lp["router"], k)
    total, loads = 0.0, []
    for rank in range(4):
        held = slice(2 * rank, 2 * rank + 2)
        y, load = moe.routed_experts(m, top_e, top_w, lp["gate"][held],
                                     lp["up"][held], lp["down"][held],
                                     first_expert=2 * rank)
        total = total + y
        loads += [int(v) for v in load]
    assert sum(loads) == n * k          # every pick landed on some chip
    # the reference normalises its input; undo that with a unit-RMS input
    unit = m * jax.lax.rsqrt(jnp.mean(m * m, -1, keepdims=True) + 1e-6)
    _e, _w = moe.route_top_k(unit, lp["router"], k)
    total_unit = sum(
        moe.routed_experts(unit, _e, _w, lp["gate"][2 * r:2 * r + 2],
                           lp["up"][2 * r:2 * r + 2],
                           lp["down"][2 * r:2 * r + 2], first_expert=2 * r)[0]
        for r in range(4))
    cfg = dict(CFG, num_experts=e, num_experts_per_tok=k, rms_norm_eps=1e-6)
    with jax.default_matmul_precision("highest"):
        want = ref.experts(m[None], lp, cfg, e, 0)[0]
    onp.testing.assert_allclose(total_unit, want, rtol=2e-4, atol=2e-5)
    assert total.shape == m.shape


def _dense_routed(m, top_e, top_w, gate, up, down, first):
    """The routed layer's equation, every held expert over every token."""
    y = 0.0
    for j in range(gate.shape[0]):
        w = jnp.sum(jnp.where(top_e == first + j, top_w, 0.0), axis=-1)
        y = y + w[:, None] * ((jax.nn.silu(m @ gate[j]) * (m @ up[j])) @ down[j])
    return y


@pytest.mark.parametrize("picks", [(5, 4), (4, 6), (6, 9), (1, 9)],
                         ids=["two-held", "first-and-last-held",
                              "one-held-one-absent", "none-held"])
def test_no_row_is_dropped_when_every_token_picks_the_same_experts(picks):
    """Every token picks the same two experts: when both are held, every
    pick of every token is a row here, the worst case the buffers are
    sized for."""
    rng = onp.random.default_rng(6)
    n, u, f, held, k = 32, 8, 4, 3, 2
    m = jnp.asarray(rng.normal(size=(n, u)), jnp.float32)
    gate, up = (jnp.asarray(rng.normal(size=(held, u, f)), jnp.float32)
                for _ in range(2))
    down = jnp.asarray(rng.normal(size=(held, f, u)), jnp.float32)
    top_e = jnp.tile(jnp.array([picks], jnp.int32), (n, 1))     # held: 4, 5, 6
    top_w = jnp.tile(jnp.array([[0.75, 0.25]], jnp.float32), (n, 1))
    y, load = moe.routed_experts(m, top_e, top_w, gate, up, down,
                                 first_expert=4)
    assert [int(v) for v in load] == [n * picks.count(e) for e in (4, 5, 6)]
    want = _dense_routed(m, top_e, top_w, gate, up, down, 4)
    onp.testing.assert_allclose(y, want, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("at_once", [65536, 16], ids=["one-part", "three-parts"])
@pytest.mark.parametrize("held_share", [0.0, 0.3, 1.0])
def test_every_pick_finds_its_sorted_row_forward_and_backward(
        held_share, at_once, monkeypatch):
    """`row_of` undoes `order`; the gathers that stand where scatter-adds
    would give the dense equation's value and its gradients in the
    activations, the pick weights and every expert matrix, with the tokens
    taken whole or in parts."""
    monkeypatch.setattr(moe, "PICKS_AT_ONCE", at_once)
    rng = onp.random.default_rng(11)
    n, u, f, held, k, first = 24, 8, 4, 3, 2, 2
    m = jnp.asarray(rng.normal(size=(n, u)), jnp.float32)
    gate, up = (jnp.asarray(rng.normal(size=(held, u, f)), jnp.float32)
                for _ in range(2))
    down = jnp.asarray(rng.normal(size=(held, f, u)), jnp.float32)
    here = rng.random((n, 1)) < held_share
    top_e = jnp.asarray(onp.where(here, [[2, 3]], [[0, 7]])
                        + (rng.random((n, 1)) < 0.5) * here, jnp.int32)
    top_w = jnp.asarray(rng.random((n, k)), jnp.float32)
    tok, order, row_of, load = moe._sorted_picks(top_e, held, first)
    assert onp.array_equal(onp.asarray(order)[onp.asarray(row_of).ravel()],
                           onp.arange(n * k))
    assert onp.array_equal(onp.asarray(tok), onp.asarray(order) // k)
    assert int(jnp.sum(load)) == 2 * int(here.sum())
    key = onp.asarray(top_e).ravel()[onp.asarray(order)]
    rows = int(jnp.sum(load))
    assert (onp.diff(key[:rows]) >= 0).all()          # live rows, by expert
    _y, counted = moe.routed_experts(m, top_e, top_w, gate, up, down, first)
    assert onp.array_equal(counted, load)             # the parts' counts, added

    def loss(fn, *a):
        return jnp.sum(jnp.sin(fn(*a)))
    args = (m, top_w, gate, up, down)
    got = jax.value_and_grad(
        lambda *a: loss(lambda m, w, *e: moe.routed_experts(
            m, top_e, w, *e, first_expert=first)[0], *a),
        argnums=(0, 1, 2, 3, 4))(*args)
    want = jax.value_and_grad(
        lambda *a: loss(lambda m, w, *e: _dense_routed(
            m, top_e, w, *e, first), *a), argnums=(0, 1, 2, 3, 4))(*args)
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        onp.testing.assert_allclose(g, w, rtol=1e-3, atol=2e-4)


@pytest.mark.parametrize("dtype,u,f", [(jnp.float32, 8, 4),
                                       (jnp.bfloat16, 128, 128)],
                         ids=["f32-tiny", "bf16-128"])
@pytest.mark.parametrize("live", [0, 1, 8, 9, 48],
                         ids=["no-pick-held", "one-row", "exactly-one-block",
                              "one-row-past-a-block", "every-pick-held"])
def test_the_loops_over_the_live_blocks_give_the_dense_layer(
        live, dtype, u, f, monkeypatch):
    """The sorted-row work runs in blocks of `ROWS_AT_ONCE` rows, as many as
    the live rows fill: 48 picks in blocks of 8, with 0, 1, 1, 2 and all 6
    blocks live.  Result and the gradients in the activations, the pick
    weights and every expert matrix against the dense equation in f32; with
    no pick held the loops make no turn and everything is exactly zero."""
    monkeypatch.setattr(moe, "ROWS_AT_ONCE", 8)
    rng = onp.random.default_rng(13)
    n, held, k, first = 24, 3, 2, 2
    m = jnp.asarray(rng.normal(size=(n, u)), dtype)
    gate, up = (jnp.asarray(rng.normal(size=(held, u, f)) * u ** -0.5, dtype)
                for _ in range(2))
    down = jnp.asarray(rng.normal(size=(held, f, u)) * f ** -0.5, dtype)
    picks = rng.choice([0, 1, 5, 6, 7], size=n * k)               # absent
    picks[rng.permutation(n * k)[:live]] = first + rng.integers(0, held, live)
    top_e = jnp.asarray(picks.reshape(n, k), jnp.int32)
    top_w = jnp.asarray(rng.random((n, k)), jnp.float32)
    ct = jnp.asarray(rng.normal(size=(n, u)), jnp.float32)
    _y, load = moe.routed_experts(m, top_e, top_w, gate, up, down, first)
    assert int(jnp.sum(load)) == live

    def both(fn, *args):
        def loss(*a):
            y = fn(*a)
            return jnp.sum(y.astype(jnp.float32) * ct), y
        (_l, y), grads = jax.value_and_grad(
            loss, argnums=(0, 1, 2, 3, 4), has_aux=True)(*args)
        return [onp.asarray(v, onp.float32) for v in (y,) + grads]

    got = both(lambda m, w, *e: moe.routed_experts(
        m, top_e, w, *e, first_expert=first)[0], m, top_w, gate, up, down)
    with jax.default_matmul_precision("highest"):
        want = both(lambda m, w, *e: _dense_routed(m, top_e, w, *e, first),
                    *(jnp.asarray(a, jnp.float32)
                      for a in (m, top_w, gate, up, down)))
    tol = 1e-4 if dtype == jnp.float32 else 3e-2
    for g, w in zip(got, want):
        if live == 0:
            assert not g.any() and not w.any()
        onp.testing.assert_allclose(g, w, rtol=tol,
                                    atol=tol * max(onp.abs(w).max(), 1e-6))


@pytest.mark.parametrize("rows_at_once", [65536, 8],
                         ids=["boundary-block", "whole-dead-blocks"])
@pytest.mark.parametrize("u,f", [(8, 4), (128, 128)],
                         ids=["ragged_dot", "pallas"])
def test_rows_that_no_group_holds_reach_nothing(monkeypatch, u, f,
                                                rows_at_once):
    """On the chip the grouped matmul leaves the rows past the last group
    unwritten, forward and backward (my chip run, PR 32: the first step's
    gradients were not finite and the step-guard held every update), and
    the buffers the loops over the live blocks fill start out undefined.
    Here a grouped matmul that writes NaN there stands in for the first,
    around whichever implementation the widths take, and buffers that start
    as NaN for the second: with all 48 picks in one block the dead rows are
    the tail of the block that holds the boundary, with blocks of 8 whole
    blocks besides, which no loop visits; NaN lies in every dead row of
    every sorted-row tensor, operands of the grouped matmuls included."""
    real = grouped_matmul.grouped_matmul
    monkeypatch.setattr(moe, "ROWS_AT_ONCE", rows_at_once)
    monkeypatch.setattr(moe, "_fresh", lambda rows, width, dtype: jnp.full(
        (rows, width), jnp.nan, dtype))

    def poison(rows, sizes):
        dead = jnp.arange(rows.shape[0]) >= jnp.sum(sizes)
        return jnp.where(dead[:, None], jnp.nan, rows)

    @jax.custom_vjp
    def leaky(lhs, rhs, sizes):
        return poison(real(lhs, rhs, sizes), sizes)

    def fwd(lhs, rhs, sizes):
        return leaky(lhs, rhs, sizes), (lhs, rhs, sizes)

    def bwd(res, ct):
        lhs, rhs, sizes = res
        _out, vjp = jax.vjp(lambda a, b: real(a, b, sizes), lhs, rhs)
        dl, dr = vjp(ct)
        return poison(dl, sizes), dr, onp.zeros(sizes.shape, jax.dtypes.float0)

    leaky.defvjp(fwd, bwd)
    rng = onp.random.default_rng(9)
    n, k = 24, 2
    m = jnp.asarray(rng.normal(size=(n, u)), jnp.float32)
    router = jnp.asarray(rng.normal(size=(u, 8)), jnp.float32)
    gate, up = (jnp.asarray(rng.normal(size=(2, u, f)), jnp.float32)
                for _ in range(2))
    down = jnp.asarray(rng.normal(size=(2, f, u)), jnp.float32)

    def loss(m, router, gate, up, down):
        top_e, top_w = moe.route_top_k(m, router, k)
        y, _load = moe.routed_experts(m, top_e, top_w, gate, up, down, 2)
        return jnp.sum(y * y)

    args = (m, router, gate, up, down)
    with monkeypatch.context() as clean:
        clean.setattr(moe, "_fresh", lambda rows, width, dtype: jnp.zeros(
            (rows, width), dtype))
        want = jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4))(*args)
    held = moe.route_top_k(m, router, k)[0]
    assert 8 < int(jnp.sum((held == 2) | (held == 3))) < 40   # dead blocks
    monkeypatch.setattr(grouped_matmul, "grouped_matmul", leaky)
    got = jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4))(*args)
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert onp.isfinite(onp.asarray(g)).all()
        onp.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-6)


def test_rows_of_absent_experts_cost_nothing_and_add_nothing():
    m = jnp.ones((6, 4), jnp.float32)
    w = jnp.ones((2, 4, 4), jnp.float32)
    top_e = jnp.full((6, 2), 7, jnp.int32).at[:, 1].set(6)     # held: 0, 1
    y, load = moe.routed_experts(m, top_e, jnp.full((6, 2), 0.5), w, w, w)
    assert [int(v) for v in load] == [0, 0] and not onp.asarray(y).any()


@pytest.mark.parametrize("fn", [rope_inv_freq, ref.rope_inv_freq],
                         ids=["system", "reference"])
def test_yarn_frequencies_against_values_worked_by_hand(fn):
    """head_dim 128, theta 500000, factor 16 over 8192, beta 32 and 1:
    dim(32) = 128 ln(8192 / 64 pi) / (2 ln 500000) = 18.08 -> low 18;
    dim(1) = 128 ln(8192 / 2 pi) / (2 ln 500000) = 34.98 -> high 35."""
    inv, scale = fn(128, ROPE["full_attention"])
    inv = onp.asarray(inv, onp.float64)
    f = 500000.0 ** (-2.0 * onp.arange(64) / 128)
    assert scale == pytest.approx(0.1 * math.log(16) + 1.0)
    onp.testing.assert_allclose(inv[:19], f[:19], rtol=1e-6)          # ramp 0 up to low
    onp.testing.assert_allclose(inv[35:], f[35:] / 16, rtol=1e-6)     # ramp 1 from high
    onp.testing.assert_allclose(inv[26], f[26] * (1 - 8 / 17 * 15 / 16), rtol=1e-6)
    assert inv[19] < f[19] * 0.99 and inv[34] > f[34] / 16 * 1.01     # pins low and high
    assert inv[18] == pytest.approx(0.0249548, rel=1e-4)              # e^(-36/128 ln 5e5)
    plain, one = fn(128, ROPE["sliding_attention"])
    onp.testing.assert_allclose(onp.asarray(plain, onp.float64), f, rtol=1e-6)
    assert one == 1.0


def test_flash_path_in_interpret_mode_equals_the_dense_path():
    x = mx.np.array(ids(2), dtype="int32")
    dense = build(4, 1, use_flash=False)(x).asnumpy()
    flash = build(4, 1, use_flash=True)(x).asnumpy()
    onp.testing.assert_allclose(flash, dense, rtol=2e-4, atol=2e-5)


def test_reference_in_blocks_of_positions_equals_the_whole():
    model = build(4, 1)
    params, x = reference_params(model), jnp.asarray(ids(4))
    whole = ref.logits(params, x, CFG, experts_held=4, ep_rank=1)
    blocks = ref.logits(params, x, CFG, experts_held=4, ep_rank=1, block=4)
    onp.testing.assert_allclose(blocks, whole, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
def test_fused_step_trains_and_writes_the_load_counters(remat):
    model = build(4, 1, remat=remat)
    mod = CausalLMLoss(model)
    trainer = mx.gluon.Trainer(model.collect_params(), "adamw",
                               {"learning_rate": 1e-2})
    step = mx.gluon.FusedTrainStep(mod, trainer)
    x = mx.np.array(ids(7), dtype="int32")
    losses = [float(step(x, batch_size=B).asnumpy()) for _ in range(4)]
    assert losses[-1] < losses[0]
    loads = [l for l in moe.expert_loads()
             if l["layer"].endswith("experts.expert_load")
             and any(l["rows"])]
    mine = loads[-4:]
    assert len(mine) == 4 and all(l["first_expert"] == 4 for l in mine)
    for l in mine:
        assert len(l["rows"]) == 4 and 0 < sum(l["rows"]) <= B * T * 2


def test_whole_layer_routes_every_pick():
    model = build()
    mod = CausalLMLoss(model)
    trainer = mx.gluon.Trainer(model.collect_params(), "adamw", {})
    mx.gluon.FusedTrainStep(mod, trainer)(mx.np.array(ids(8), dtype="int32"),
                                          batch_size=B)
    rows = model.layer0.experts.expert_load.data().asnumpy()
    assert rows.sum() == B * T * CFG["num_experts_per_tok"]


def test_expert_loads_publishes_the_live_share_of_the_picks():
    """Half the tokens pick experts 0 and 2, half 0 and 1; the layer holds
    2 and 3: a quarter of the step's picks are rows here, and
    `expert_loads()` says so in what it returns, in the
    `mxtpu_moe_live_row_share` gauge and on the `moe.load` event."""
    from mxnet_tpu import observe, telemetry
    from mxnet_tpu.parallel import RoutedExperts
    layer = RoutedExperts(8, 4, 8, 2, experts_held=2, ep_rank=1)
    layer.initialize()
    router = onp.zeros((8, 8), "float32")
    router[0, [0, 2]], router[1, [0, 1]] = (5.0, 4.0), (5.0, 4.0)
    layer.router.set_data(mx.np.array(router))
    x = onp.zeros((4, 10, 8), "float32")
    x[:2, :, 0], x[2:, :, 1] = 1.0, 1.0
    with mx.autograd.record():
        layer(mx.np.array(x))
    name = layer.expert_load.name
    mine, = [l for l in moe.expert_loads() if l["layer"] == name]
    assert mine["rows"] == [20, 0] and layer.picks == 80
    assert mine["live_row_share"] == 0.25
    assert telemetry.default_registry().get_sample_value(
        "mxtpu_moe_live_row_share", {"layer": name}) == 0.25
    assert any(e[4:6] == ("moe", "moe.load") and e[6]["layer"] == name
               and e[6]["live_row_share"] == 0.25 for e in observe.events())
