"""Does the training path still start on the chip?  The quickest proof.

    python chip_smoke.py             # one chip (what the driver runs)
    python chip_smoke.py --chips 4   # only the four-chip phase; about 24
                                     # minutes, most of them the classic
                                     # path's small per-device compiles
    python chip_smoke.py --decoder-reference [CONFIG]   # only a decoder
                                     # against its plain f32 reference at the
                                     # published widths, one sequence of its
                                     # cell (mellum2_12b_a2p5b: 8192 tokens;
                                     # kimi_linear_48b_a3b: 16,384)

Drives model zoo -> `Trainer` -> `gluon.FusedTrainStep` on an attached TPU
through the entry points a user calls, at full width, and checks what comes
out against references in the tree.  One process, nothing spawned that needs
the chip.  Prints one JSON line per phase and, as its last line, exactly
`{"ok": ..., "device": {"platform", "kind", "count"}}`.  Exits non-zero when
jax finds no TPU or when any phase failed; no phase carries on without a
chip, none falls back to a smaller size.  Step times are printed for the
`waitall` check and as evidence the steps ran; they are not measurements of
speed (the benchmark's job) and are recorded nowhere as such.
"""
from __future__ import annotations

import argparse
import functools
import json
import math
import os
import sys
import time
import traceback

import numpy as onp

ROOT = os.path.dirname(os.path.abspath(__file__))
BATCH = 128          # ResNet-50 b128 bf16: fixed, an OOM is a failure
EAGER_BATCH = 32
IMAGE = 224
FLASH_SHAPE = (4, 12, 2048, 64)   # (B, H, T, D) of the BERT T=2048 step
WINDOW_SHAPE = (32, 4, 128, 1024)    # (H, Hkv, D, window) of a decoder's window layer, at T=2048
BERT_BATCH, BERT_SEQ = 4, 2048
# the decoder cell's grouped matmuls: a part's sorted rows and how many of
# them are live, held experts, hidden size, expert width
GROUPED_SHAPE = (65536, 35000, 16, 2304, 896)


class Failed(Exception):
    """A phase's check did not hold."""


def check(cond, what):
    if not cond:
        raise Failed(what)


def rel_err(got, want):
    got = onp.asarray(got, onp.float64)
    want = onp.asarray(want, onp.float64)
    return float(onp.linalg.norm(got - want) /
                 max(onp.linalg.norm(want), 1e-30))


def memory_stat(dev, key):
    return dev.memory_stats()[key]


def compiled_step(fused, *args, batch_size):
    """The step's compiled program, with what the checks read off it.  The
    AOT compile of a step that already ran is served by the persistent
    cache."""
    compiled = fused.lower(*args, batch_size=batch_size).compile()
    text = compiled.as_text()
    return {
        "tpu_custom_calls": text.count("tpu_custom_call"),
        "all_reduces": text.count(" all-reduce(") +
        text.count(" all-reduce-start("),
        "program_temp_bytes": compiled.memory_analysis().temp_size_in_bytes,
    }, compiled


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------
def phase_device(ctx):
    import jax
    import jaxlib

    import mxnet_tpu as mx
    from mxnet_tpu import _native
    from mxnet_tpu.analysis.census import peaks_key

    try:
        import libtpu
        libtpu_version = libtpu.__version__
    except ImportError:
        libtpu_version = None
    dev = jax.devices()[0]
    stats = dev.memory_stats()
    check(mx.num_tpus() == len(jax.devices()), "mx.num_tpus() != device count")
    check(mx.current_context() == mx.tpu(0),
          f"default context is {mx.current_context()}, not tpu(0)")
    return {
        "jax": jax.__version__, "jaxlib": jaxlib.__version__,
        "libtpu": libtpu_version, "device_kind": dev.device_kind,
        "peaks_key": peaks_key(dev.device_kind),  # unknown kind: an error
        "memory_stats": stats is not None,
        "bytes_limit": (stats or {}).get("bytes_limit"),
        "compile_cache_dir": jax.config.jax_compilation_cache_dir,
        "compile_cache_from_env": bool(
            os.environ.get("JAX_COMPILATION_CACHE_DIR")),
        "native_lib_loaded": _native.lib() is not None,
    }


def _dense_attention(q, k, v, mask):
    """Plain attention in f32 with a key-padding mask: the reference the
    flash kernel is held to."""
    import jax
    import jax.numpy as jnp
    hi = jax.lax.Precision.HIGHEST
    qf, kf, vf = (a.astype(jnp.float32) for a in (q, k, v))
    s = jnp.einsum("bhqd,bhkd->bhqk", qf, kf, precision=hi) * \
        q.shape[-1] ** -0.5
    s = jnp.where(mask[:, None, None, :] != 0, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, vf, precision=hi)


def _grouped_matmul_numbers(key):
    """`ops.grouped_matmul` at the decoder cell's shapes, uneven groups: its
    result and both cotangents against `lax.ragged_dot`'s (largest error
    over the live rows, relative to the largest value) and the time of the
    three together, each way."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops import grouped_matmul as gm

    m, live_rows, held, u, f = GROUPED_SHAPE
    kl, *keys = jax.random.split(key, 7)
    share = jax.random.gamma(kl, 1.5, (held,))
    load = jnp.floor(share / share.sum() * live_rows).astype(jnp.int32)
    live = (jnp.arange(m) < load.sum())[:, None]

    def three(matmul):
        def run(rows, weights, ct):
            y, vjp = jax.vjp(lambda a, b: matmul(a, b, load), rows, weights)
            d_rows, d_weights = vjp(ct)
            return (jnp.where(live, y, 0), jnp.where(live, d_rows, 0),
                    d_weights)
        return jax.jit(run)

    def timed(fn, *args):
        jax.block_until_ready(fn(*args))
        t0 = time.perf_counter()
        for _ in range(10):
            got = fn(*args)
        jax.block_until_ready(got)
        return got, (time.perf_counter() - t0) / 10 * 1e3

    out = {"load": [int(v) for v in load]}
    for (k, n), (kr, kw, kc) in zip(((u, 2 * f), (f, u)),
                                    (keys[:3], keys[3:])):
        rows = jax.random.normal(kr, (m, k), jnp.float32).astype(jnp.bfloat16)
        weights = (jax.random.normal(kw, (held, k, n), jnp.float32)
                   * k ** -0.5).astype(jnp.bfloat16)
        ct = jnp.where(live, jax.random.normal(kc, (m, n), jnp.float32),
                       0).astype(jnp.bfloat16)
        got, ms = timed(three(gm.grouped_matmul), rows, weights, ct)
        want, ragged_ms = timed(three(functools.partial(
            jax.lax.ragged_dot, precision=jax.lax.Precision.DEFAULT)),
            rows, weights, ct)
        errs = [float(jnp.max(jnp.abs(g.astype(jnp.float32)
                                      - w.astype(jnp.float32)))
                      / jnp.max(jnp.abs(w.astype(jnp.float32))))
                for g, w in zip(got, want)]
        out[f"{k}x{n}"] = {"max_err": dict(zip(
            ("rows_x_w", "rows_x_wt", "rowst_x_rows"), errs)),
            "ms": round(ms, 3), "ragged_dot_ms": round(ragged_ms, 3)}
        check(max(errs) < 1e-2, f"grouped matmul {k}x{n}: {out}")
    return out


def phase_kernels(ctx):
    """The path's Pallas kernels, flash attention and the grouped matmul,
    compiled (`interpret=False` passed, or the TPU's own choice), against
    their XLA forms."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops import pallas_kernels as pk

    out = {}
    key = jax.random.key(ctx["seed"])
    out["grouped_matmul"] = _grouped_matmul_numbers(jax.random.fold_in(key, 3))

    # flash attention, the shape and mask the BERT T=2048 step runs
    b, h, t, d = FLASH_SHAPE
    kq, kk, kv, kd = jax.random.split(jax.random.fold_in(key, 1), 4)
    q, k, v = (jax.random.normal(kx, (b, h, t, d), jnp.float32)
               .astype(jnp.bfloat16) for kx in (kq, kk, kv))
    lens = onp.random.RandomState(ctx["seed"]).randint(t // 2, t + 1, size=b)
    mask = jnp.asarray((onp.arange(t)[None, :] < lens[:, None])
                       .astype(onp.int32))

    flash = functools.partial(pk.flash_attention, mask=mask, interpret=False)

    def grads(attention, qkv=(q, k, v)):
        def loss(q, k, v):
            return (attention(q, k, v).astype(jnp.float32) ** 2).sum()
        return jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(*qkv)

    want_fwd = jax.jit(_dense_attention)(q, k, v, mask)
    out["flash_fwd_rel_err"] = rel_err(
        jax.jit(flash)(q, k, v).astype(jnp.float32), want_fwd)
    check(out["flash_fwd_rel_err"] < 2e-2, f"flash forward: {out}")
    want_g = grads(lambda q, k, v: _dense_attention(q, k, v, mask)
                   .astype(jnp.bfloat16))
    for name, g, w in zip(("dq", "dk", "dv"), grads(flash), want_g):
        e = rel_err(g.astype(jnp.float32), w.astype(jnp.float32))
        out[f"flash_{name}_rel_err"] = e
        check(e < 4e-2, f"flash {name}: rel err {e}")

    # grouped-query heads under a sliding window, as a decoder's window
    # layer runs them: 32 query heads on 4 key-value heads, 1024 keys seen
    hq, hkv, dw, window = WINDOW_SHAPE
    kq, kk, kv = jax.random.split(jax.random.fold_in(key, 2), 3)
    qw = jax.random.normal(kq, (1, hq, t, dw), jnp.float32).astype(jnp.bfloat16)
    kw, vw = (jax.random.normal(kx, (1, hkv, t, dw), jnp.float32)
              .astype(jnp.bfloat16) for kx in (kk, kv))

    def dense_window(q, k, v):
        hi = jax.lax.Precision.HIGHEST
        q, k, v = (a.astype(jnp.float32) for a in (q, k, v))
        k, v = (jnp.repeat(a, hq // hkv, axis=1) for a in (k, v))
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k, precision=hi) * dw ** -0.5
        dist = jnp.arange(t)[:, None] - jnp.arange(t)[None, :]
        p = jax.nn.softmax(
            jnp.where((dist >= 0) & (dist < window), s, -1e30), axis=-1)
        return jnp.einsum("bhqk,bhkd->bhqd", p, v, precision=hi)

    windowed = functools.partial(pk.flash_attention, causal=True,
                                 window=window, interpret=False)
    out["flash_window_gqa_fwd_rel_err"] = rel_err(
        jax.jit(windowed)(qw, kw, vw).astype(jnp.float32),
        jax.jit(dense_window)(qw, kw, vw))
    check(out["flash_window_gqa_fwd_rel_err"] < 2e-2,
          f"windowed grouped-query flash forward: {out}")
    want_w = grads(lambda q, k, v: dense_window(q, k, v)
                   .astype(jnp.bfloat16), (qw, kw, vw))
    for name, g, w in zip(("dq", "dk", "dv"),
                          grads(windowed, (qw, kw, vw)), want_w):
        e = rel_err(g.astype(jnp.float32), w.astype(jnp.float32))
        out[f"flash_window_gqa_{name}_rel_err"] = e
        check(e < 4e-2, f"windowed grouped-query flash {name}: rel err {e}")

    # once more with dropout 0.1: v == 1 makes each output row its kept
    # weight mass over keep, so the mean reads the keep-rate
    dropping = functools.partial(flash, dropout=0.1, key=kd)
    dropped = jax.jit(dropping)(q, k, jnp.ones_like(v)).astype(jnp.float32)
    check(bool(jnp.isfinite(dropped).all()), "flash+dropout: not finite")
    out["flash_dropout_mean_mass"] = float(dropped.mean())
    check(abs(out["flash_dropout_mean_mass"] - 1.0) < 0.01,
          f"flash+dropout keep-rate: mean mass {out}")
    check(all(bool(jnp.isfinite(g.astype(jnp.float32)).all())
              for g in grads(dropping)),
          "flash+dropout backward: not finite")
    return out


def _resnet_step(ctxs, init=None, recipe=None):
    """(net, module-with-loss, trainer, fused step) of ResNet-50 bf16 under
    SGD+momentum, parameters set from ``init`` (name -> host array) so that
    every path compared starts from the same point."""
    import mxnet_tpu as mx
    from mxnet_tpu.gluon import loss as gloss
    from mxnet_tpu.gluon.block import HybridBlock
    from mxnet_tpu.gluon.model_zoo import vision

    class NetWithLoss(HybridBlock):
        def __init__(self, net, loss_fn):
            super().__init__()
            self.net = net
            self.loss_fn = loss_fn

        def forward(self, x, y):
            return self.loss_fn(self.net(x), y)

    net = vision.resnet50_v1()
    net.initialize(init=mx.init.Xavier(), ctx=ctxs)
    net.cast("bfloat16")
    mod = NetWithLoss(net, gloss.SoftmaxCrossEntropyLoss())
    # the zoo model infers its channel counts: one forward of one image
    # settles every deferred shape, op by op, before anything is compared
    net(mx.np.zeros((1, 3, IMAGE, IMAGE), dtype="bfloat16", ctx=ctxs[0]))
    if init is not None:
        for name, p in net.collect_params().items():
            p.set_data(mx.np.array(init[name], dtype=init[name].dtype))
    trainer = mx.gluon.Trainer(
        net.collect_params(), "sgd",
        {"learning_rate": 0.01, "momentum": 0.9},
        kvstore="tpu_ici" if len(ctxs) > 1 else "device")
    fused = None if len(ctxs) > 1 else \
        mx.gluon.FusedTrainStep(mod, trainer, recipe=recipe)
    return net, mod, trainer, fused


def _batch(seed, n, ctx=None):
    import mxnet_tpu as mx
    rs = onp.random.RandomState(seed)
    x = rs.uniform(-1, 1, (n, 3, IMAGE, IMAGE)).astype(onp.float32)
    y = rs.randint(0, 1000, (n,))
    return (mx.np.array(x, dtype="bfloat16", ctx=ctx),
            mx.np.array(y, dtype="int32", ctx=ctx))


def _snapshot(net):
    return {k: p.data().asnumpy() for k, p in net.collect_params().items()}


def phase_resnet50_train(ctx):
    import jax

    import mxnet_tpu as mx
    from mxnet_tpu import autograd

    out = {}
    tpu0 = mx.tpu(0)           # named, not defaulted
    dev = tpu0.jax_device()
    mx.random.seed(ctx["seed"])
    x, y = _batch(ctx["seed"], BATCH, tpu0)

    net, _mod, _tr, fused = _resnet_step([tpu0])
    init = _snapshot(net)

    def step_and_wait():
        loss = fused(x, y, batch_size=BATCH)
        return float(loss.asnumpy().astype(onp.float64).mean())

    t0 = time.perf_counter()
    losses = [step_and_wait()]
    first = time.perf_counter() - t0
    for _ in range(4):
        losses.append(step_and_wait())
    out["losses"] = [round(v, 4) for v in losses]
    check(all(math.isfinite(v) for v in losses), f"loss not finite: {losses}")
    check(losses[4] < losses[0], f"loss did not fall: {losses}")

    params = list(net.collect_params().values())
    off = [p.name for p in params if p.data()._data.devices() != {dev}]
    check(not off, f"parameters not on {dev}: {off[:5]}")
    out["params"] = len(params)

    # does waitall() drain on this backend?  Every timing in the repo ends
    # in it.  Against block_until_ready of all the step wrote.
    def timed(wait):
        t0 = time.perf_counter()
        loss = fused(x, y, batch_size=BATCH)
        dispatched = time.perf_counter() - t0
        wait(loss)
        return time.perf_counter() - t0, dispatched

    def wait_bur(loss):
        jax.block_until_ready([loss._data] + [p.data()._data for p in params])

    mx.waitall()
    bur, wall, disp = [], [], []
    for _ in range(3):
        t, d = timed(wait_bur)
        bur.append(t)
        disp.append(d)
        t, d = timed(lambda _loss: mx.waitall())
        wall.append(t)
    out["step_s_block_until_ready"] = sorted(bur)[1]
    out["step_s_waitall"] = sorted(wall)[1]
    out["dispatch_s"] = sorted(disp)[1]
    out["compile_s"] = first - out["step_s_block_until_ready"]
    a, b = out["step_s_block_until_ready"], out["step_s_waitall"]
    check(abs(a - b) <= 0.2 * max(a, b),
          f"waitall ({b:.4f}s) and block_until_ready ({a:.4f}s) disagree")
    out["peak_bytes_in_use"] = memory_stat(dev, "peak_bytes_in_use")
    out["bytes_in_use"] = memory_stat(dev, "bytes_in_use")

    t0 = time.perf_counter()
    out.update(compiled_step(fused, x, y, batch_size=BATCH)[0])
    out["aot_recompile_s"] = time.perf_counter() - t0
    check(out["tpu_custom_calls"] == 0,
          "a tpu_custom_call in the ResNet-50 step: BatchNorm's backward "
          "is XLA's fused reduction, the model holds no kernel")

    # the eager path the example uses, from the same initial parameters,
    # against the fused step at its batch
    xe, ye = _batch(ctx["seed"] + 1, EAGER_BATCH, tpu0)
    _n, _m, _t, fused32 = _resnet_step([tpu0], init)
    fused_first = float(fused32(xe, ye, batch_size=EAGER_BATCH)
                        .asnumpy().astype(onp.float64).mean())
    net_e, _m, trainer_e, _f = _resnet_step([tpu0], init)
    net_e.hybridize(static_alloc=True)
    loss_fn = mx.gluon.loss.SoftmaxCrossEntropyLoss()
    eager = []
    for _ in range(2):
        with autograd.record():
            loss = loss_fn(net_e(xe), ye)
        loss.backward()
        trainer_e.step(EAGER_BATCH)
        eager.append(float(loss.asnumpy().astype(onp.float64).mean()))
    out["eager_losses"] = [round(v, 4) for v in eager]
    out["fused_b32_first_loss"] = round(fused_first, 4)
    check(all(math.isfinite(v) for v in eager), f"eager loss: {eager}")
    check(abs(eager[0] - fused_first) <= 2e-2 * abs(fused_first),
          f"eager first loss {eager[0]} != fused {fused_first}")
    return out


def bert_flash_step(seed, batch, seq, **overrides):
    """(step, batch) of the benchmark's bert_base held to the flash kernel:
    `chipbench/configs/bert_base.json` with positions for `seq` and
    `use_flash` true, built by `chipbench`'s own `build`, on one ragged batch
    (valid lengths seq/2..seq) from its `make_ring`; attention dropout 0.1
    and the key-padding mask run in the kernel.  ``overrides`` replace other
    fields of the configuration (a test cuts the depth)."""
    import mxnet_tpu as mx
    from chipbench.configs import bert_base

    with open(os.path.join(ROOT, "chipbench", "configs", "bert_base.json")) as f:
        cfg = json.load(f)
    cfg.update(max_position_embeddings=seq, use_flash=True, **overrides)
    mx.random.seed(seed)
    mod, trainer = bert_base.build(cfg)
    cell = {"ring": 1, "batch": batch, "seq_len": seq,
            "valid_lengths": [seq // 2, seq]}
    (arrays, _valid), = bert_base.make_ring(cfg, cell, 1, seed, None)
    return mx.gluon.FusedTrainStep(mod, trainer), arrays


def phase_bert_flash(ctx):
    """One bert_base pretraining step on the flash path at B=4, T=2048."""
    b = BERT_BATCH
    step, batch = bert_flash_step(ctx["seed"], b, BERT_SEQ)
    out = {}
    t0 = time.perf_counter()
    losses = [float(step(*batch, batch_size=b).asnumpy())]
    out["first_step_s"] = time.perf_counter() - t0
    for _ in range(2):
        losses.append(float(step(*batch, batch_size=b).asnumpy()))
    out["losses"] = [round(v, 4) for v in losses]
    check(all(math.isfinite(v) for v in losses), f"loss not finite: {losses}")
    out.update(compiled_step(step, *batch, batch_size=b)[0])
    check(out["tpu_custom_calls"] > 0,
          "no tpu_custom_call in the step: the flash path did not engage")
    return out


def phase_memory_profile(ctx):
    """`profiler.dump_memory_profile` on this backend (it used to be refused
    by platform string)."""
    import tempfile

    from mxnet_tpu import profiler

    with tempfile.TemporaryDirectory() as d:
        path = profiler.dump_memory_profile(os.path.join(d, "memory.pprof"))
        return {"pprof_bytes": os.path.getsize(path)}


def phase_resnet50_dp4(ctx):
    """`FusedTrainStep(recipe="dp4")` at global batch 128 against the
    one-device step on the same parameters and batch, then the classic
    multi-context kvstore path."""
    import jax

    import mxnet_tpu as mx
    from mxnet_tpu import autograd
    from mxnet_tpu.gluon.utils import split_and_load

    out = {}
    devs = jax.devices()
    check(len(devs) == 4, f"--chips 4 needs four devices, jax has {len(devs)}")
    tpu0 = mx.tpu(0)
    mx.random.seed(ctx["seed"])
    x, y = _batch(ctx["seed"], BATCH, tpu0)

    def three(fused):
        return [float(fused(x, y, batch_size=BATCH).asnumpy()
                      .astype(onp.float64).mean()) for _ in range(3)]

    net1, _m, _t, fused1 = _resnet_step([tpu0])
    init = _snapshot(net1)
    one = three(fused1)
    out["one_chip"] = compiled_step(fused1, x, y, batch_size=BATCH)[0]

    net4, _m, _t, fused4 = _resnet_step([tpu0], init, recipe="dp4")
    dp4 = three(fused4)
    per_sample = fused4(x, y, batch_size=BATCH)._data   # stays dp-sharded
    shards = per_sample.addressable_shards
    check(len({s.device for s in shards}) == 4 and
          all(s.data.shape == (BATCH // 4,) for s in shards),
          f"per-sample loss is not 4 shards of {BATCH // 4} on 4 devices")
    out["one_chip_losses"] = [round(v, 4) for v in one]
    out["dp4_losses"] = [round(v, 4) for v in dp4]
    # the first loss is a forward of equal parameters; after that two bf16
    # trajectories drift apart by what their reductions round differently
    for i, (a, b) in enumerate(zip(one, dp4)):
        tol = 5e-2 if i else 1e-2
        check(math.isfinite(b) and abs(a - b) <= tol * abs(a),
              f"step {i + 1}: dp4 loss {b} != one-chip loss {a}")

    out["dp4"], compiled = compiled_step(fused4, x, y, batch_size=BATCH)
    check(out["dp4"]["all_reduces"] > 0, "no all-reduce in the dp4 step")
    check(out["one_chip"]["tpu_custom_calls"] == 0 and
          out["dp4"]["tpu_custom_calls"] == 0,
          "a tpu_custom_call in the ResNet-50 step, one chip or dp4")
    in_shardings = jax.tree_util.tree_leaves(compiled.input_shardings[0])
    x_sh = [s for s in in_shardings
            if s.shard_shape(x.shape) == (BATCH // 4,) + x.shape[1:]]
    check(x_sh and len(x_sh[0].device_set) == 4,
          "the step's image input is not four shards on four devices")
    w = next(iter(net4.collect_params().values())).data()._data
    check(len(w.sharding.device_set) == 4, "dp4 parameters not on 4 devices")
    in_use = [memory_stat(d, "bytes_in_use") for d in devs]
    out["bytes_in_use"] = in_use
    check(all(v > 0 for v in in_use), f"a device holds nothing: {in_use}")

    # classic path: one parameter copy per context, grads summed by the
    # tpu_ici kvstore
    ctxs = [mx.tpu(i) for i in range(4)]
    net_c, _m, trainer_c, _f = _resnet_step(ctxs)
    net_c.hybridize(static_alloc=True)
    loss_fn = mx.gluon.loss.SoftmaxCrossEntropyLoss()
    xe, ye = _batch(ctx["seed"] + 1, EAGER_BATCH)
    weight = next(p for p in net_c.collect_params().values()
                  if p.grad_req != "null")
    before = weight.data(ctxs[0]).asnumpy().astype(onp.float32)
    classic = []
    for _ in range(2):
        xs, ys = split_and_load(xe, ctxs), split_and_load(ye, ctxs)
        with autograd.record():
            losses = [loss_fn(net_c(xb), yb).mean() for xb, yb in zip(xs, ys)]
        autograd.backward(losses)
        trainer_c.step(EAGER_BATCH)
        classic.append(sum(float(v.asnumpy()) for v in losses) / len(losses))
    out["classic_losses"] = [round(v, 4) for v in classic]
    check(all(math.isfinite(v) for v in classic), f"classic loss: {classic}")
    where = {next(iter(weight.data(c)._data.devices())) for c in ctxs}
    check(where == set(devs), f"classic path parameters live on {where}")
    copies = [weight.data(c).asnumpy().astype(onp.float32) for c in ctxs]
    out["classic_weight_moved"] = float(onp.abs(copies[0] - before).sum())
    check(out["classic_weight_moved"] > 0, "the classic path updated nothing")
    check(all(onp.array_equal(copies[0], c) for c in copies[1:]),
          "the classic path's parameter copies fell out of sync")
    return out

# ---------------------------------------------------------------------------
# A decoder cell against its plain f32 reference (`--decoder-reference
# [CONFIG]`).  Tolerances, each with its reason.
# mellum2_12b_a2p5b (readings: my chip runs, PR 32):
#   logits, given the system's picks: rms error <= 0.01 and largest <= 0.05 on
#     logits of rms 0.96 -- bf16 rounds at 2^-9 relative, through four layers
#     and the head; read 0.0053 and 0.036.  The reference is GIVEN the
#     system's picks, so a flipped near-tie of the router hides no error.
#   gradients (relative L2 <= 0.10, cosine >= 0.995): a bf16 backward pass
#     through the same depth; read 1.4-6.0% and >= 0.998.
#   the control: the reference with its expert weights cast to fp8 (e4m3),
#     the nearest precision below the stated one, must FAIL the logits'
#     tolerance -- else the check could not tell the precisions apart.
# kimi_linear_48b_a3b (readings: my chip runs, PR 34; PERF.md section 6): the
#   same four limits, five layers deep; the control casts every layer's q
#   (KDA's k and v too) projection and every SwiGLU's matrices (the dense
#   layer's, the shared and the routed experts') to fp8.
# xing4_29b_a4b (readings on a TPU v5 lite, PERF.md section 6): Kimi's limits,
#   both heads held to them;
#   read: main head rms 0.0132, max 0.088; MTP head 0.0110, 0.072; gradients
#   1.0-1.6%, cosine >= 0.99987; the control (every MLA projection and SwiGLU
#   matrix in fp8) 0.150 and 1.04, outside both logits limits.
LOGITS_RMS_TOL, LOGITS_MAX_TOL = 0.01, 0.05
GRAD_REL_TOL, GRAD_COS_TOL = 0.10, 0.995
DECODER_CELL = "mellum2_12b_a2p5b.sft_t8192_ep4share"
DECODER_GRADS = ("layer0.attend.attention.query.weight",
                 "layer0.attend.attention.key.weight", "layer0.experts.router",
                 "layer0.experts.gate", "layer0.experts.up",
                 "layer0.experts.down", "layer3.attend.attention.query.weight")


def _mellum_reference_params(p, cfg, _cfgmod):
    layers = []
    for l in range(cfg["num_hidden_layers"]):
        a, e = f"layer{l}.attend.attention.", f"layer{l}.experts."
        layers.append({
            "norm1": p[f"layer{l}.attend.norm.gamma"],
            "wq": p[a + "query.weight"].T, "wk": p[a + "key.weight"].T,
            "wv": p[a + "value.weight"].T, "wo": p[a + "proj.weight"].T,
            "norm2": p[f"layer{l}.ffn_norm.gamma"], "router": p[e + "router"],
            "gate": p[e + "gate"], "up": p[e + "up"], "down": p[e + "down"]})
    return {"embed": p["embed.weight"], "norm": p["norm.gamma"],
            "head": p["head.weight"].T, "layers": layers}


def _plain_walk(model, ids, _cfgmod):
    """((the logits,), [(layer, its FFN's normed input)]) of a `DecoderLM`
    with one residual stream, its blocks called one after another."""
    x, inputs = model.embed(ids), []
    for name in model._layer_names:
        layer = getattr(model, name)
        x = x + layer.attend(x)
        m = layer.ffn_norm(x)
        inputs.append((layer, m))
        for part in layer._ffn:
            x = x + getattr(layer, part)(m)
    return (model.head(model.norm(x)),), inputs


# per configuration: its cell, the reference's module, how the system's
# parameters and configuration reach the reference, the parameters whose
# gradients are compared as (system name, layer, reference key, transposed),
# and the reference keys the fp8 control casts
DECODER_REFERENCES = {
    "mellum2_12b_a2p5b": dict(
        cell=DECODER_CELL, reference="mellum2", top_k="num_experts_per_tok",
        params=_mellum_reference_params,
        config=lambda cfg, cfgmod: dict(
            cfg, num_experts=cfg["num_experts_routed"],
            layer_types=cfg["layer_types"][:cfg["num_hidden_layers"]]),
        grads=[(DECODER_GRADS[0], 0, "wq", True), (DECODER_GRADS[1], 0, "wk", True),
               (DECODER_GRADS[2], 0, "router", False), (DECODER_GRADS[3], 0, "gate", False),
               (DECODER_GRADS[4], 0, "up", False), (DECODER_GRADS[5], 0, "down", False),
               (DECODER_GRADS[6], 3, "wq", True)],
        fp8=("gate", "up", "down")),
    "kimi_linear_48b_a3b": dict(
        cell="kimi_linear_48b_a3b.sft_t16384_ep32share", reference="kimi_linear",
        top_k="num_experts_per_token", block=256, logits_tol=(0.04, 0.25),
        params=lambda p, cfg, cfgmod: cfgmod.reference_params(p, cfg),
        config=lambda cfg, cfgmod: cfgmod.reference_config(cfg),
        grads=[("layer0.attend.attention.q_proj.weight", 0, "wq", True),
               ("layer0.attend.attention.k_conv", 0, "conv_k", False),
               ("layer0.attend.attention.f_b.weight", 0, "wf_b", True),
               ("layer0.attend.attention.A_log", 0, "A_log", False),
               ("layer0.attend.attention.b_proj.weight", 0, "wb", True),
               ("layer0.mlp.down.weight", 0, "down", True),
               ("layer1.attend.attention.v_proj.weight", 1, "wv", True),
               ("layer1.experts.router", 1, "router", False),
               ("layer1.experts.gate", 1, "gate", False),
               ("layer1.experts.down", 1, "down", False),
               ("layer1.shared.up.weight", 1, "shared_up", True),
               ("layer3.attend.attention.q_proj.weight", 3, "wq", True),
               ("layer3.attend.attention.kv_b.weight", 3, "wkvb", True),
               ("layer4.attend.attention.g_b.weight", 4, "wg_b", True)],
        fp8=("wq", "wk", "wv", "gate", "up", "down", "shared_gate",
             "shared_up", "shared_down")),
    "xing4_29b_a4b": dict(
        cell="xing4_29b_a4b.sft_t8192_ep8share", reference="xing4",
        top_k="num_experts_per_tok", held="n_routed_experts", block=256,
        logits_tol=(0.04, 0.25),
        walk=lambda model, ids, cfgmod: cfgmod.walk(model, ids),
        params=lambda p, cfg, cfgmod: cfgmod.reference_params(p, cfg),
        config=lambda cfg, cfgmod: cfgmod.reference_config(cfg),
        grads=[("layer1.attend.hc.phi", 1, ("hc_attn", "phi"), False),
               ("layer0.attend.attention.q_a.weight", 0, "wqa", True),
               ("layer2.attend.attention.kv_a.weight", 2, "wkva", True),
               ("layer1.experts.router", 1, "router", False),
               ("layer1.experts.gate", 1, "gate", False),
               ("layer3.ffn_hc.phi", 3, ("hc_ffn", "phi"), False)],
        fp8=("wqa", "wqb", "wkva", "wkvb", "wo", "gate", "up", "down",
             "shared_gate", "shared_up", "shared_down")),
}


def decoder_reference_numbers(cfg, cell, cfgmod, block=None,
                              config="mellum2_12b_a2p5b"):
    """The decoder built by the cell's own `build` on sequence 0 of ring
    batch 0, against its plain reference (`models/reference/`) given the
    system's picks: logits, loss, the gradients of the configuration's
    `grads` (attentions', a router's and the held experts' matrices), and
    the fp8 control."""
    import importlib

    import jax
    import jax.numpy as jnp

    import mxnet_tpu as mx
    from mxnet_tpu.ndarray.ndarray import NDArray
    from mxnet_tpu.parallel import moe

    spec = DECODER_REFERENCES[config]
    block = block or spec.get("block", 512)    # positions the reference takes at once
    ref = importlib.import_module("mxnet_tpu.models.reference." + spec["reference"])
    loss_block, _trainer = cfgmod.build(cfg)
    model = loss_block.model
    ids = NDArray(cfgmod.make_ring(cfg, cell, 1, 0, None)[0][0][0]._data[:1])
    held, rank = cfg[spec.get("held", "num_experts")], cfg["ep_rank"]
    top_k = cfg[spec["top_k"]]
    ref_cfg = spec["config"](cfg, cfgmod)
    share = dict(experts_held=held, ep_rank=rank, block=block)

    # the system, layer by layer through its own blocks, for its picks
    heads, inputs = spec.get("walk", _plain_walk)(model, ids, cfgmod)
    picks = []
    for layer, m in inputs:
        experts = getattr(layer, "experts", None)
        if experts is None:
            picks.append(None)
        else:
            bias = getattr(experts, "correction_bias", None)
            e, _w = moe.route_top_k(
                m._data.reshape(ids.shape[1], -1), experts.router.data()._data,
                top_k, experts._scoring[0],
                None if bias is None else bias.data()._data,
                *experts._scoring[1:])
            picks.append(e.reshape(1, ids.shape[1], top_k))
    got = [h._data.astype(jnp.float32) for h in heads]

    # the compiled loss rounds otherwise than the eager walk and would flip
    # near-ties of its own: its routers are given the walk's picks, layer
    # by layer in the order they are traced, as the reference is
    given = [pk.reshape(-1, top_k) for pk in picks if pk is not None]
    route, traced = moe.route_top_k, []
    for layer, _m in inputs:    # each traced for itself, not through a like layer's trace
        layer.like = ()

    def route_given(*args, **kwargs):
        traced.append(None)
        return route(*args, picks=given[(len(traced) - 1) % len(given)], **kwargs)

    loss_block.hybridize()
    moe.route_top_k = route_given
    try:
        with mx.autograd.record():
            loss = loss_block(ids)
        loss.backward()
    finally:
        moe.route_top_k = route
    check(len(traced) == len(given), f"{len(traced)} routers traced, {len(given)} given picks")
    params = model.collect_params()
    mine = {n: params[n].grad()._data.astype(jnp.float32)
            for n, *_ in spec["grads"]}

    p = {k: v.data()._data.astype(jnp.float32) for k, v in params.items()}
    del loss, loss_block, model, params, heads, inputs, layer, experts, m, _trainer  # room
    rp = spec["params"](p, cfg, cfgmod)
    del p

    def as_heads(out):      # a configuration with a prediction head gives two
        return out if isinstance(out, tuple) else (out,)
    ref_logits = jax.jit(lambda rp: as_heads(ref.logits(rp, ids._data, ref_cfg,
                                                        picks=picks, **share)))

    def cast8(x):
        return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)

    def xent(lg):
        logp = jax.nn.log_softmax(lg[:, :-1], axis=-1)
        return float(-jnp.mean(jnp.take_along_axis(
            logp, ids._data[:, 1:, None], axis=-1)))

    def errors(lgs, wants):
        """The main head's errors; a second head's (its positions that
        predict a token, T - 1 of them) under `mtp_logits_err`."""
        out = {}
        for name, lg, want in zip(("logits_err", "mtp_logits_err"), lgs, wants):
            d = jnp.abs(lg[:, :want.shape[1]] - want)
            out[name] = {"rms": float(jnp.sqrt(jnp.mean(d * d))), "max": float(d.max())}
        return out

    want = ref_logits(rp)
    out = {"config": config, "tokens": int(ids.shape[1]),
           "logits_rms": float(jnp.sqrt(jnp.mean(want[0] * want[0]))),
           **errors(got, want),
           "loss_reference_f32": xent(want[0]), "loss_system": xent(got[0])}
    # the reference left to its own picks: what the cell's `loss_band` is set from
    out["loss_reference_f32_own_picks"] = float(jax.jit(
        lambda rp: ref.loss(rp, ids._data, ref_cfg, **share))(rp))
    def fp8(lp):
        return dict(lp, **{k: cast8(lp[k]) for k in spec["fp8"] if k in lp})
    control = dict(rp, layers=[fp8(lp) for lp in rp["layers"]])
    out["fp8_control_logits_err"] = errors(ref_logits(control), want)["logits_err"]
    del got, want
    if "mtp" in rp:
        control["mtp"] = dict(rp["mtp"], layer=fp8(rp["mtp"]["layer"]))
    # the band's other reading: the reference in the precision below the stated one
    out["loss_fp8_control_own_picks"] = float(jax.jit(
        lambda rp: ref.loss(rp, ids._data, ref_cfg, **share))(control))
    del control

    def ref_loss(sub, rp):      # sub[j]: the j-th compared parameter's value
        changed = [dict(lp) for lp in rp["layers"]]
        for j, (_n, l, key, _t) in enumerate(spec["grads"]):
            if isinstance(key, tuple):       # a parameter inside a group
                changed[l][key[0]] = dict(changed[l][key[0]], **{key[1]: sub[j]})
            else:
                changed[l][key] = sub[j]
        return ref.loss(dict(rp, layers=changed), ids._data, ref_cfg,
                        picks=picks, **share)

    def leaf(lp, key):
        return lp[key[0]][key[1]] if isinstance(key, tuple) else lp[key]
    g = jax.jit(jax.grad(ref_loss))(
        [leaf(rp["layers"][l], key) for _n, l, key, _t in spec["grads"]], rp)
    out["gradients"] = {}
    for j, (n, l, key, transposed) in enumerate(spec["grads"]):
        a, b = mine[n], g[j].T if transposed else g[j]
        out["gradients"][n] = {
            "rel_l2": float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b)),
            "cosine": float(jnp.sum(a * b) / (jnp.linalg.norm(a)
                                              * jnp.linalg.norm(b)))}
    return out


def phase_decoder_reference(ctx):
    """At the published widths and one sequence of the cell's tokens (the
    system's forward runs its kernels: flash, the grouped matmuls, KDA's
    scan)."""
    from chipbench import run
    config = ctx.get("decoder_reference") or "mellum2_12b_a2p5b"
    _manifest, _chips, cell, cfg, cfgmod = run.load_cell(
        DECODER_REFERENCES[config]["cell"])
    out = decoder_reference_numbers(cfg, cell, cfgmod, config=config)
    print(json.dumps({"phase": "decoder_reference", "numbers": out}), flush=True)
    ctl = out["fp8_control_logits_err"]
    rms_tol, max_tol = DECODER_REFERENCES[config].get(
        "logits_tol", (LOGITS_RMS_TOL, LOGITS_MAX_TOL))
    for head in ("logits_err", "mtp_logits_err"):
        err = out.get(head, {"rms": 0.0, "max": 0.0})
        check(err["rms"] <= rms_tol and err["max"] <= max_tol,
              f"{head} against the reference given the system's picks: {out}")
    check(ctl["rms"] > rms_tol or ctl["max"] > max_tol,
          f"an fp8 cast of the control's weights passes the logits' tolerance: {out}")
    for n, g in out["gradients"].items():
        check(g["rel_l2"] <= GRAD_REL_TOL and g["cosine"] >= GRAD_COS_TOL,
              f"gradient of {n} against the reference: {g}")
    return out


# ---------------------------------------------------------------------------
def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip phase (resnet50_dp4)")
    ap.add_argument("--decoder-reference", nargs="?", metavar="CONFIG",
                    const="mellum2_12b_a2p5b", choices=sorted(DECODER_REFERENCES),
                    help="run only a decoder cell against its f32 reference "
                         "(mellum2_12b_a2p5b where no configuration is named)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    if device["platform"] != "tpu" or len(devs) < args.chips:
        print(json.dumps({"phase": "device", "ok": False,
                          "error": f"need {args.chips} TPU device(s), jax "
                                   f"found {device}"}))
        print(json.dumps({"ok": False, "device": device}))
        return 1

    phases = [phase_device] + (
        [phase_resnet50_dp4] if args.chips == 4 else
        [phase_decoder_reference] if args.decoder_reference else
        [phase_kernels, phase_resnet50_train, phase_bert_flash,
         phase_memory_profile])
    ctx = {"seed": args.seed, "decoder_reference": args.decoder_reference}
    ok = True
    for phase in phases:
        row = {"phase": phase.__name__[len("phase_"):], "ok": True}
        t0 = time.perf_counter()
        try:
            row.update(phase(ctx))
        except Exception as e:  # reported, and the run exits non-zero
            traceback.print_exc()
            row["ok"] = ok = False
            row["error"] = f"{type(e).__name__}: {e}"[:2000]
        row["seconds"] = round(time.perf_counter() - t0, 3)
        print(json.dumps(row), flush=True)
        if not ok and phase is phase_device:
            break
    print(json.dumps({"ok": ok, "device": device}))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
