"""Expert parallelism: a mixture-of-experts FFN sharded over an ``ep``
mesh axis.

Absent from the reference (predates MoE).  TPU-native form: expert weight
tensors carry a leading experts axis sharded over ``ep``; tokens are
dispatched with a one-hot routing einsum, so XLA's SPMD partitioner
inserts the all-to-all/all-reduce over ICI — the "annotate shardings, let
XLA place collectives" recipe rather than hand-written NCCL groups.

`routed_experts` is the sparse form (a softmax or sigmoid top-k router
`route_top_k`, sorted dispatch, the
grouped matmul of `ops/grouped_matmul.py` over the experts held): a chip is
told which contiguous experts it holds, routes over all of them and
computes its own part of the result.  `moe_ffn` is the older dense top-1
toy.
"""
from __future__ import annotations

import functools
import weakref

import jax
import jax.numpy as jnp
import numpy as onp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..ops import grouped_matmul as _gm
from ..ops.pallas_kernels import _pick_block

__all__ = ["moe_ffn", "init_moe_params", "moe_partition_specs",
           "shard_moe_params", "route_top_k", "routed_experts",
           "expert_loads"]


def init_moe_params(key, num_experts, d_model, d_hidden, dtype=jnp.float32):
    """(router, w1 (E, D, H), b1 (E, H), w2 (E, H, D), b2 (E, D))."""
    k0, k1, k2 = jax.random.split(key, 3)
    s = d_model ** -0.5
    return {
        "router": jax.random.normal(k0, (d_model, num_experts), dtype) * s,
        "w1": jax.random.normal(k1, (num_experts, d_model, d_hidden),
                                dtype) * s,
        "b1": jnp.zeros((num_experts, d_hidden), dtype),
        "w2": jax.random.normal(k2, (num_experts, d_hidden, d_model),
                                dtype) * (d_hidden ** -0.5),
        "b2": jnp.zeros((num_experts, d_model), dtype),
    }


def moe_partition_specs(axis_name="ep"):
    """PartitionSpecs for `init_moe_params` output: experts axis sharded."""
    e = P(axis_name)
    return {"router": P(), "w1": e, "b1": e, "w2": e, "b2": e}


def shard_moe_params(params, mesh, axis_name="ep"):
    specs = moe_partition_specs(axis_name)
    return {
        k: jax.device_put(v, NamedSharding(mesh, specs[k]))
        for k, v in params.items()
    }


def moe_ffn(params, x, capacity_factor=None, router_noise=0.0, key=None):
    """Top-1 (switch) MoE FFN: x (B, T, D) -> (B, T, D), plus the load-
    balancing auxiliary loss (Switch Transformer, Fedus et al.).

    Dense dispatch: tokens are combined with a one-hot routing matrix in an
    einsum over the experts axis.  With `w1/w2` sharded over ``ep``, XLA
    partitions the expert dimension and inserts the collectives; no
    explicit all_to_all is written.  `capacity_factor` is accepted for API
    familiarity and unused (dense dispatch has no token dropping).
    """
    del capacity_factor
    if router_noise > 0.0 and key is None:
        raise ValueError("router_noise > 0 requires a PRNG `key`")
    b, t, d = x.shape
    e = params["w1"].shape[0]
    logits = x @ params["router"]                          # (B, T, E)
    if router_noise > 0.0:
        logits = logits + router_noise * jax.random.normal(
            key, logits.shape, logits.dtype)
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    expert_idx = jnp.argmax(probs, axis=-1)                # (B, T)
    onehot = jax.nn.one_hot(expert_idx, e, dtype=x.dtype)  # (B, T, E)
    gate = jnp.take_along_axis(
        probs, expert_idx[..., None], axis=-1)[..., 0].astype(x.dtype)

    # dispatch -> expert FFN -> combine, all as expert-axis einsums
    xe = jnp.einsum("btd,bte->ebtd", x, onehot)
    h = jax.nn.gelu(jnp.einsum("ebtd,edh->ebth", xe, params["w1"])
                    + params["b1"][:, None, None, :])
    ye = jnp.einsum("ebth,ehd->ebtd", h, params["w2"]) \
        + params["b2"][:, None, None, :]
    y = jnp.einsum("ebtd,bte->btd", ye, onehot) * gate[..., None]

    # Switch load-balancing loss: E * sum_e f_e * p_e
    frac_tokens = onehot.astype(jnp.float32).mean(axis=(0, 1))   # (E,)
    frac_probs = probs.mean(axis=(0, 1))                         # (E,)
    aux_loss = e * jnp.sum(frac_tokens * frac_probs)
    return y, aux_loss


# ---------------------------------------------------------------------------
# Sparse routed experts: softmax top-k, sorted dispatch, grouped matmul.
# ---------------------------------------------------------------------------
# The sorted rows' buffers hold the worst case (every pick of every token on
# a held expert: no row is dropped at any imbalance), so the tokens go through
# in parts of at most this many picks, one part after another.  Set in the
# decoder cell's step (16,384 tokens, top-8, 16 of 64 experts held; PR 32).
# Compiled for the described v5e: all 131,072 picks at once need 17.11 GiB of
# 15.75 with scatter-adds and fit with these gathers only by XLA's own
# rematerialisation; two parts of 65,536 take 6.43 GB of temporaries.  On the
# chip, against three capacities (5/16, 1/2, 1 of the worst case) under
# `lax.switch` with scatter-adds, same seed, AdamW 1e-4: 23,753 against 21,905
# tokens/s, p95 731 against 851 ms; a step of 0.613 against 0.586 s at 138k
# rows, 0.719 against 0.835 s at 280k, that is 0.78 against 1.7 ms per 1,000
# rows and no cliff where a capacity ends (my chip runs, PR 32; the grouped
# matmul was `lax.ragged_dot` then).  With `ops/grouped_matmul.py`'s kernels
# the same two parts take 6.55 GB of temporaries and the cell reads 30,344
# and 30,416 tokens/s against 23,751 and 23,853 (my chip runs, PR 33).
# A layer may take parts of its own size (`routed_experts(picks_at_once=)`),
# where a model's other state leaves no room for these buffers.
PICKS_AT_ONCE = 65536
# What XLA runs on a part's sorted rows (the gather in, SwiGLU and its
# gradient, the cotangent rows) runs in blocks of this many rows, as many
# blocks as the live rows fill (`_live_blocks`); fitted to small parts by
# `_pick_block`, and a multiple of the grouped matmul's row tile
# (`ops/grouped_matmul.py::_ROWS`).  Picked from runs of both decoder cells
# (my chip runs, PR 37, TPU v5 lite).  One layer, value and gradient, 16,384
# tokens, ms at 1024 / 2048 / 4096 / 8192 / 16384 / 32768 rows: 48.21 / 48.12
# / 47.94 / 46.46 / 47.72 / 54.39 with 35,053 live rows a part in 16 uneven
# groups (the Mellum cell's shapes; 55.89 before, over the whole buffers),
# 20.89 / 20.99 / 21.20 / 22.12 / 23.56 / 28.16 with 2,047 live rows a part in
# 8 groups (the Kimi cell's; 37.50 before): a turn costs some 10 to 20 us
# beside its rows, a row rounded up 0.1 us.  The cells themselves, one seed
# each, at 2048 against 8192: Mellum 32,554 against 32,485 tokens/s (30,180
# before), Kimi 15,220 against 15,179 (14,168 before).
ROWS_AT_ONCE = 2048


def route_top_k(m, router, top_k, scoring="softmax", bias=None,
                renormalize=True, scale=1.0, picks=None):
    """(experts (N, k) int32, weights (N, k) f32) for activations m (N, U)
    and router (U, E), scored over ALL experts in f32.

    ``scoring="softmax"``: the `top_k` largest probabilities, renormalised
    to sum to one (`norm_topk_prob`).  ``"sigmoid"``: scores s =
    sigmoid(m router); the `top_k` largest of s + ``bias`` (E,) are chosen
    (the correction bias steers the CHOICE only and takes no gradient),
    their weights are the unbiased s, divided by their sum (+ 1e-20) where
    ``renormalize``, times ``scale`` (`routed_scaling_factor`).

    ``picks`` (N, k) overrides WHICH experts are chosen — their weights are
    still this router's — so that two programs whose scores differ in the
    last bit can be compared on one choice (`chip_smoke.py`)."""
    logits = jnp.dot(m.astype(jnp.float32), router.astype(jnp.float32))
    if scoring == "softmax":
        p = jax.nn.softmax(logits, axis=-1)
        if picks is None:
            top_p, top_e = jax.lax.top_k(p, top_k)
        else:
            top_e, top_p = picks, jnp.take_along_axis(p, picks, axis=-1)
        return top_e, top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    if scoring != "sigmoid":
        raise ValueError(f"scoring {scoring!r} is neither softmax nor sigmoid")
    s = jax.nn.sigmoid(logits)
    if picks is None:
        biased = s if bias is None else \
            s + jax.lax.stop_gradient(bias.astype(jnp.float32))
        _, top_e = jax.lax.top_k(jax.lax.stop_gradient(biased), top_k)
    else:
        top_e = picks
    top_s = jnp.take_along_axis(s, top_e, axis=-1)
    if renormalize:
        top_s = top_s / (jnp.sum(top_s, axis=-1, keepdims=True) + 1e-20)
    return top_e, top_s * scale


def _sorted_picks(top_e, held, first_expert):
    """Picks sorted by held expert (stable: a group's rows keep token
    order; picks of absent experts sort last): (token of each sorted row,
    flat pick of each sorted row, sorted row of each pick (N, k), rows per
    held expert)."""
    k = top_e.shape[1]
    local = top_e.reshape(-1) - first_expert
    key = jnp.where((local >= 0) & (local < held), local, held)
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    row_of = jnp.argsort(order).astype(jnp.int32).reshape(top_e.shape)
    load = jnp.sum(key[:, None] == jnp.arange(held, dtype=key.dtype)[None, :],
                   axis=0, dtype=jnp.int32)
    return order // k, order, row_of, load


def _fresh(rows, width, dtype):
    """A whole-part buffer of sorted rows for a loop over the live blocks to
    fill.  Its content is undefined (`AllocateBuffer` on the TPU, no write
    pass): the loop writes a block before anything reads it, and a dead
    row is nobody's.  Made inside a part's body, and the body is no
    function that jax differentiates (`_routed`'s rules loop over the parts
    themselves): under `jax.grad` of a `lax.map` over the parts jax hoists
    what depends on nothing out of the loop, and each part then COPIES the
    buffer back in, 0.9 ms for 65,536 rows of 2304 (my chip run, PR 37)."""
    return jax.lax.empty((rows, width), dtype)


def _live_blocks(fn, live, *buffers):
    """The sorted-row work that XLA runs: ``fn(cut, *blocks) -> blocks``
    over row blocks of `_pick_block(picks, ROWS_AT_ONCE)` rows, block i
    for i < ceil(live / rows of a block), a loop whose trip count comes from
    the load.  ``buffers`` are whole-part arrays of sorted rows and keep
    their worst-case shape; each turn hands `fn` its block of every buffer
    and writes what `fn` returns back in its place, and ``cut(a)`` is the
    same rows of any other whole-part array.  Blocks past the last live one
    are neither read nor written."""
    picks = buffers[0].shape[0]
    rows = _pick_block(picks, ROWS_AT_ONCE)

    def turn(i, buffers):
        def cut(a):
            return jax.lax.dynamic_slice_in_dim(a, i * rows, rows)
        blocks = fn(cut, *map(cut, buffers))
        return tuple(
            jax.lax.dynamic_update_slice_in_dim(b, new.astype(b.dtype),
                                                i * rows, 0)
            for b, new in zip(buffers, blocks))

    return jax.lax.fori_loop(0, (live + rows - 1) // rows, turn, buffers)


def _swiglu(z):
    f = z.shape[-1] // 2
    return jax.nn.silu(z[:, :f]) * z[:, f:]


def _rows_through_experts(m, tok, live, load, gate, up, down):
    """The live sorted rows through their experts' SwiGLU: two grouped
    matmuls over the rows present, gate and up side by side in the first
    (one pass over the rows forward, one cotangent of them backward), and
    between the kernels only the live blocks.  Returns the experts' rows
    and, for the backward rule (a loop of a traced length has no reverse
    rule, so it takes the pieces), gate | up's rows z and the two matmuls'
    vjps."""
    xs, = _live_blocks(lambda cut, _: (m[cut(tok)],), live,
                       _fresh(tok.shape[0], m.shape[1], m.dtype))
    z, vjp_in = jax.vjp(lambda a, w: _gm.grouped_matmul(a, w, load),
                        xs, jnp.concatenate([gate, up], axis=-1))
    h, = _live_blocks(lambda cut, _: (_swiglu(cut(z)),), live,
                      _fresh(z.shape[0], gate.shape[-1], z.dtype))
    out, vjp_out = jax.vjp(lambda a, w: _gm.grouped_matmul(a, w, load),
                           h, down)
    return out, z, vjp_in, vjp_out


def _gather_sum(rows, row_of, live, weights=None):
    """sum_j weights[t, j] * rows[row_of[t, j]] in f32: (N, U), over the
    picks whose sorted row is live.  THIS is where a row that does not
    exist becomes zero: a pick of an absent expert sorts past the live rows,
    where the grouped matmuls write nothing and no loop goes, so what it
    fetches may be NaN and is SELECTED away (never multiplied by 0).
    Gathers, one pick at a time (nothing of picks x U is held), never a
    scatter-add: on the chip a scatter-add of 32,768 rows of 2304 takes
    4.9 ms and its time follows the rows it is GIVEN, live or not (my chip
    run, PR 32)."""
    total = 0.0
    for j in range(row_of.shape[1]):
        picked = jnp.where((row_of[:, j] < live)[:, None],
                           rows[row_of[:, j]], 0).astype(jnp.float32)
        total = total + (picked if weights is None
                         else picked * weights[:, j:j + 1])
    return total


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _routed(m, top_e, top_w, gate, up, down, first_expert):
    """(y, load) of every part: m, top_e, top_w are (parts, tokens of a
    part, ·).  Both rules loop over the parts themselves, one part after
    another; the backward rule adds the parts' weight gradients up in the
    weights' dtype, as the transpose of a scan would."""
    return _routed_fwd(m, top_e, top_w, gate, up, down, first_expert)[0]


def _routed_fwd(m, top_e, top_w, gate, up, down, first_expert):
    def part(args):
        m, top_e, top_w = args
        tok, order, row_of, load = _sorted_picks(top_e, gate.shape[0],
                                                 first_expert)
        live = jnp.sum(load)
        out = _rows_through_experts(m, tok, live, load, gate, up, down)[0]
        y = _gather_sum(out, row_of, live, top_w)
        return y.astype(m.dtype), load, tok, order, row_of

    y, load, *sorting = jax.lax.map(part, (m, top_e, top_w))
    # the backward pass recomputes the experts' hidden rows from these (the
    # flash kernels' bargain): nothing the size of the sorted rows is kept
    return (y, load), (m, top_w, gate, up, down, load, *sorting)


def _routed_bwd(first_expert, res, cts):
    m, top_w, gate, up, down, *sorting = res

    def part(dweights, args):
        m, top_w, dy, load, tok, order, row_of = args
        live = jnp.sum(load)
        out, z, vjp_in, vjp_out = _rows_through_experts(m, tok, live, load,
                                                        gate, up, down)

        def into_rows(cut, out, _):
            d = dy[cut(tok)].astype(jnp.float32)          # of each sorted row
            # the weight of a pick multiplies its row: its gradient is found
            # on the sorted side and each pick fetches its own
            return (top_w.reshape(-1)[cut(order)][:, None] * d,
                    jnp.sum(out.astype(jnp.float32) * d, axis=-1))

        # each cotangent takes the place of the rows it is the cotangent of
        dout, dw = _live_blocks(into_rows, live, out,
                                jnp.zeros(tok.shape, jnp.float32))
        dh, ddown = vjp_out(dout)
        dz, = _live_blocks(lambda cut, z: jax.vjp(_swiglu, z)[1](cut(dh)),
                           live, z)
        dxs, dboth = vjp_in(dz)
        dm = _gather_sum(dxs, row_of, live).astype(m.dtype)
        f = gate.shape[-1]
        sums = tuple(a + b for a, b in zip(
            dweights, (dboth[..., :f], dboth[..., f:], ddown)))
        return sums, (dm, jnp.where(row_of < live, dw[row_of], 0))

    dweights, (dm, dw) = jax.lax.scan(
        part, tuple(jnp.zeros_like(w) for w in (gate, up, down)),
        (m, top_w, cts[0], *sorting))
    return (dm, onp.zeros(sorting[-1].shape, jax.dtypes.float0), dw,
            *dweights)


_routed.defvjp(_routed_fwd, _routed_bwd)


def routed_experts(m, top_e, top_w, gate, up, down, first_expert=0,
                   picks_at_once=None):
    """The part of a routed SwiGLU layer that the experts held here give.

    m (N, U) activations; top_e/top_w (N, k) from `route_top_k`; gate, up
    (held, U, F) and down (held, F, U) are experts ``first_expert ..
    first_expert + held - 1`` of the layer.  Returns

        y[t] = sum over picks (t, e) with e held of
               w[t, e] * down_e(silu(gate_e m[t]) * up_e m[t])

    in m's dtype, and ``load`` (held,) int32, the rows each held expert
    received.  Picks are sorted by expert, rows of absent experts sort
    last and are never touched; the matmuls are
    `ops.grouped_matmul.grouped_matmul` over the sorted rows: Pallas
    kernels that visit only the row tiles present (`ragged_gmm`,
    `ragged_gmm_t`, `ragged_tgmm` custom calls in the trace), or
    `lax.ragged_dot` where a width is no multiple of 128 lanes.  One
    straight-line path, run over the tokens in parts of ``picks_at_once``
    picks (`PICKS_AT_ONCE` where None), whose buffers hold a part's worst
    case (every pick of every token on a held expert), so no row is dropped
    at any imbalance;
    each token gathers its picks' rows back, forward and backward, and
    nothing scatters.

    The work follows the LIVE rows, ``sum(load)`` of a part, a prefix of
    its buffers: the kernels visit the tiles present, and what XLA runs
    between them (the gather in, SwiGLU and its gradient, the cotangent
    rows, the pick weights' gradient) runs in `_live_blocks`, a loop over
    blocks of `ROWS_AT_ONCE` rows whose trip count comes from ``load``.
    Rows past the live ones are undefined in every sorted-row tensor (a
    kernel writes none, no loop visits a dead block, the tail of the block
    that holds the boundary is whatever the arithmetic made of it) and may
    be NaN.  The zero of a pick whose expert is absent is made in ONE place,
    where a token gathers its picks back (`_gather_sum`, and the pick
    weights' gradient beside it): a select on ``row_of < sum(load)``, never
    a multiply, so that neither a result nor a cotangent of a row that does
    not exist reaches a token or a weight.  The grouped matmuls take
    operands with such rows (`ops/grouped_matmul.py`).

    With ``held == num_experts`` this is the whole layer (every row live,
    every block visited); nothing stands in for absent chips or their
    exchange.  Differentiable in m, top_w and the weights; the backward
    pass recomputes the hidden rows, and its rule is written out (a loop of
    a traced length has no reverse rule).
    """
    return _routed_in_parts(
        m, top_e, top_w, gate, up, down, first_expert,
        (picks_at_once or PICKS_AT_ONCE, ROWS_AT_ONCE, _fresh,
         _gm.grouped_matmul, _gm._context.on_tpu()))


@functools.partial(jax.jit, static_argnames=("first_expert", "reads"))
def _routed_in_parts(m, top_e, top_w, gate, up, down, first_expert, reads):
    """`routed_experts`, jitted so that a model's routed layers of one shape
    share one trace and one lowering (four in either decoder cell's step:
    the loops over the live blocks made each 0.3 s longer to trace; my chip
    runs, PR 37).  ``reads`` is everything the trace reads from the modules
    that is no argument, and part of the jit's key for that alone: a test
    that swaps one of them is traced again; its first is the part's
    picks."""
    n = m.shape[0]
    parts = -(-top_e.size // reads[0])
    while n % parts:
        parts += 1

    y, load = _routed(*(a.reshape(parts, n // parts, a.shape[-1])
                        for a in (m, top_e, top_w)),
                      gate, up, down, first_expert)
    return y.reshape(m.shape), jnp.sum(load, axis=0)


# every live Gluon layer that routes, for `expert_loads`
_ROUTED_LAYERS = weakref.WeakSet()


def expert_loads():
    """[{"layer": name, "first_expert": i, "rows": [per held expert],
    "live_row_share": rows / picks}] of every live `RoutedExperts` layer,
    from the counter its last step wrote.  The step never waits for the
    host: the counters leave it as auxiliary state, and THIS call is the
    read — it also publishes the `mxtpu_moe_*` gauges and one `moe.load`
    flight-recorder event per layer.  ``live_row_share`` is the share of
    the step's picks (tokens x top-k, the sorted rows' buffers) that
    landed on an expert held here: how much of the buffers
    `routed_experts`' loops over the live row blocks had to visit."""
    from .. import observe, telemetry
    layers = sorted((l for l in _ROUTED_LAYERS
                     if l.expert_load._data is not None),   # initialised
                    key=lambda l: l.expert_load.name)
    loads = jax.device_get([l.expert_load.data()._data for l in layers])
    rows_g = telemetry.gauge(
        "mxtpu_moe_routed_rows", "rows routed to the experts held here, "
        "last step", labelnames=("layer",))
    skew_g = telemetry.gauge(
        "mxtpu_moe_load_max_over_mean", "most loaded held expert over the "
        "mean held expert, last step", labelnames=("layer",))
    share_g = telemetry.gauge(
        "mxtpu_moe_live_row_share", "rows on the experts held here over "
        "the picks routed (tokens x top-k), last step",
        labelnames=("layer",))
    out = []
    for layer, load in zip(layers, loads):
        name, load = layer.expert_load.name, [int(v) for v in load]
        total = sum(load)
        share = total / layer.picks if layer.picks else 0.0
        rows_g.labels(layer=name).set(total)
        skew_g.labels(layer=name).set(
            max(load) * len(load) / total if total else 0.0)
        share_g.labels(layer=name).set(share)
        observe.record("moe", "moe.load", layer=name, rows=load,
                       first_expert=layer.first_expert,
                       live_row_share=share)
        out.append({"layer": name, "first_expert": layer.first_expert,
                    "rows": load, "live_row_share": share})
    return out
