"""Capture jaxprs + HLO of the real entry points, with their contracts.

Every capture function returns a plain dict spec::

    {"name": "allreduce.bucket_dense", "kind": "allreduce",
     "jaxpr": "...", "lowered": "...", "optimized": "...",
     "contract": {...}, "meta": {...}}

``lowered`` is the pre-optimization HLO (the user program as written —
dtype intent lives here), ``optimized`` the compiled, scheduled module
(collective census, schedule, partitioning live here).  Contracts are
pinned literals, not derived at capture time wherever possible: a
contract computed from the same code it checks can never catch a
regression in that code.  The one exception is the bucketed-step
census, which is derived from the ``GradBucketer`` *plan* and then
cross-checked against the pinned PR 4 headline (160 tensors -> 4
buckets at 1 MB) by ``tests/test_hloscan.py``.

Everything lowers on the virtual 8-device CPU mesh
(``--xla_force_host_platform_device_count=8``), same as tests and the
driver dryrun — no TPU needed.
"""
from __future__ import annotations

import os

_ENTRYPOINTS = {}

#: Bucket cap of the captured bucketed step: at 1 MB the profile's 160
#: gradient tensors pack into 4 all-reduce launches (a count of the
#: program, which hloscan holds; no collective has been timed on this
#: machine outside the dp4 cell, ROADMAP D1).
BUCKETED_STEP_BUCKET_BYTES = 1 << 20

#: A ResNet-50-like gradient profile: element counts of 160 tensors.
RESNET50_PROFILE = [256] * 104 + [1024] * 26 + [16384] * 22 + [65536] * 8


def _entrypoint(name):
    def deco(fn):
        _ENTRYPOINTS[name] = fn
        return fn
    return deco


def entrypoint_names():
    return sorted(_ENTRYPOINTS)


def _ensure_virtual_mesh(n=8):
    """Force the 8-device CPU mesh before the first backend init — the
    same steering tests/conftest.py applies."""
    # mxlint: disable=env-read-at-trace-time -- pre-backend-init launcher plumbing: must read current flags each call, never traced
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={n}").strip()
    # mxlint: disable=env-read-at-trace-time -- same launcher plumbing: respect an explicit platform choice per invocation
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax
    if jax.local_device_count() < n:
        raise RuntimeError(
            f"analysis capture needs >= {n} devices for the dp mesh, got "
            f"{jax.local_device_count()} — jax initialized before the "
            f"virtual-mesh flags landed (import mxnet_tpu.analysis "
            f"earlier, or export XLA_FLAGS/JAX_PLATFORMS as tools/ci.sh "
            f"does)")


def _stage_texts(traced):
    """(jaxpr, lowered, optimized) texts from a ``jax.stages.Traced``."""
    lowered = traced.lower()
    compiled = lowered.compile()
    return (str(traced.jaxpr),
            lowered.compiler_ir(dialect="hlo").as_hlo_text(),
            compiled.as_text())


def _capture_jit(jitted, args, name, kind, contract, meta=None):
    jaxpr, low, opt = _stage_texts(jitted.trace(*args))
    return {"name": name, "kind": kind, "jaxpr": jaxpr, "lowered": low,
            "optimized": opt, "contract": contract, "meta": meta or {}}


# --------------------------------------------------------------------------
# fused SPMD train step
# --------------------------------------------------------------------------
def build_dp_fused_step():
    """The canonical dp-mesh FusedTrainStep (small MLP + loss on the
    8-device mesh).  Shared by the hloscan capture below and the
    layerscope census (`analysis/census.py`) so both fence the SAME
    program.  Returns ``(fused, (x, y), batch_size, meta)``."""
    import numpy as onp

    import mxnet_tpu as mx
    from mxnet_tpu.gluon import FusedTrainStep, Trainer, loss as gloss, nn
    from mxnet_tpu.gluon.block import HybridBlock
    from mxnet_tpu.parallel import mesh as pmesh

    class _NetWithLoss(HybridBlock):
        def __init__(self):
            super().__init__()
            self.d1 = nn.Dense(16, in_units=8)
            self.d2 = nn.Dense(8, in_units=16)
            self.loss_fn = gloss.SoftmaxCrossEntropyLoss()

        def forward(self, x, y):
            return self.loss_fn(self.d2(self.d1(x)), y)

    rng = onp.random.RandomState(7)
    mod = _NetWithLoss()
    mod.initialize()
    tr = Trainer(mod.collect_params(), "sgd",
                 {"learning_rate": 0.1, "momentum": 0.9})
    mesh = pmesh.make_mesh({"dp": 8})
    fused = FusedTrainStep(mod, tr, mesh=mesh)
    x = mx.np.array(rng.uniform(-1, 1, (16, 8)).astype(onp.float32))
    y = mx.np.array(rng.randint(0, 8, (16,)), dtype="int32")
    return fused, (x, y), 16, {"mesh": "dp:8", "params": 4, "batch": 16}


@_entrypoint("fused_train_step.dp")
def _capture_fused_train_step():
    """FusedTrainStep(mesh=dp) on a small MLP: the single donated XLA
    program a data-parallel training step dispatches.  The captured
    program is built by FusedTrainStep._prepare itself — identical arg
    treatment to a live step, not a reconstruction."""
    fused, args, batch_size, _meta = build_dp_fused_step()
    traced = fused.trace(*args, batch_size=batch_size)
    jaxpr, low, opt = _stage_texts(traced)
    # census: the partitioner derives one gradient all-reduce per
    # trainable tensor (4: two weights + two biases; the per-sample loss
    # output stays dp-sharded, so no extra loss reduction) and XLA's
    # combiner merges them.  The number is therefore the toolchain's as
    # much as the program's: 1 under jax 0.9.0 (all four in one launch;
    # 0.4.37 left four).  What the pin guards is that the step still
    # synchronizes (not 0) and that launches do not leak back in.
    return {
        "name": "fused_train_step.dp", "kind": "train_step",
        "jaxpr": jaxpr, "lowered": low, "optimized": opt,
        "contract": {
            "expect_overlap": True,
            "resharding_free": True,
            "expected_collectives": {"all-reduce": 1},
        },
        "meta": {"mesh": "dp:8", "params": 4, "batch": 16},
    }


def build_recipe_fused_step():
    """The recipe-built dp2.tp2 FusedTrainStep: the same small MLP as
    `build_dp_fused_step`, but the whole SPMD setup comes from the one
    config string — mesh, collected Dense rules, strict coverage audit,
    input spec.  d2 takes a row-split override (Megatron column->row
    pair), exercising user-override precedence over the block defaults.
    Returns ``(fused, (x, y), batch_size, meta)``."""
    import numpy as onp
    from jax.sharding import PartitionSpec as P

    import mxnet_tpu as mx
    from mxnet_tpu.gluon import FusedTrainStep, Trainer, loss as gloss, nn
    from mxnet_tpu.gluon.block import HybridBlock

    class _NetWithLoss(HybridBlock):
        def __init__(self):
            super().__init__()
            self.d1 = nn.Dense(16, in_units=8)
            self.d2 = nn.Dense(8, in_units=16)
            self.loss_fn = gloss.SoftmaxCrossEntropyLoss()

        def forward(self, x, y):
            return self.loss_fn(self.d2(self.d1(x)), y)

    rng = onp.random.RandomState(7)
    mod = _NetWithLoss()
    mod.initialize()
    tr = Trainer(mod.collect_params(), "sgd",
                 {"learning_rate": 0.1, "momentum": 0.9})
    fused = FusedTrainStep(
        mod, tr, recipe="dp2.tp2",
        partition_rules=[(r"d2\.weight$", P(None, "tp")),
                         (r"d2\.bias$", P())])
    x = mx.np.array(rng.uniform(-1, 1, (16, 8)).astype(onp.float32))
    y = mx.np.array(rng.randint(0, 8, (16,)), dtype="int32")
    return fused, (x, y), 16, {"mesh": "dp:2,tp:2", "recipe": "dp2.tp2",
                               "params": 4, "batch": 16}


@_entrypoint("fused_train_step.recipe_tp2")
def _capture_recipe_fused_step():
    """FusedTrainStep(recipe="dp2.tp2") on the small MLP: the compiled
    tensor-parallel step a recipe builds, captured through the same
    `_prepare` path a live step dispatches.  The resharding_free pin is
    the recipe subsystem's compile-time fence: if rule collection or
    placement ever disagrees with what the program computes, GSPMD
    inserts reshard transfers and this artifact fails the scan."""
    fused, args, batch_size, meta = build_recipe_fused_step()
    traced = fused.trace(*args, batch_size=batch_size)
    jaxpr, low, opt = _stage_texts(traced)
    # census: one gradient psum per trainable tensor (4 — tp-sharded
    # grads still psum, over the dp axis only) plus the Megatron pair's
    # activation all-reduces in forward and backward (row-split d2
    # partial outputs, column-split d1 input grads, and the loss
    # reduction), as XLA combines them on the 2x2 mesh: 3 issues under
    # jax 0.9.0 (0.4.37 left 8 — like the dp step's, the number is the
    # combiner's as much as the program's), no all-gather / all-to-all /
    # collective-permute (resharding-free).
    return {
        "name": "fused_train_step.recipe_tp2", "kind": "train_step",
        "jaxpr": jaxpr, "lowered": low, "optimized": opt,
        "contract": {
            "expect_overlap": True,
            "resharding_free": True,
            "expected_collectives": {"all-reduce": 3},
        },
        "meta": meta,
    }


# --------------------------------------------------------------------------
# kvstore collectives
# --------------------------------------------------------------------------
def _ici_devices():
    import jax

    return tuple(jax.local_devices()[:8])


@_entrypoint("allreduce.bucket_dense")
def _capture_allreduce_dense():
    """One dense bucket reduce: the `_allreduce_fn` shard_map+psum
    program the kvstore dispatches per bucket."""
    import jax.numpy as jnp
    import numpy as onp

    from mxnet_tpu.kvstore.tpu_ici import _allreduce_fn

    devices = _ici_devices()
    shape = (16384,)
    allreduce, sharding, _mesh = _allreduce_fn(
        devices, shape, onp.dtype(onp.float32))
    import jax
    spec = jax.ShapeDtypeStruct((len(devices),) + shape, jnp.float32,
                                sharding=sharding)
    return _capture_jit(
        allreduce, (spec,), "allreduce.bucket_dense", "allreduce",
        contract={
            # a bucket reduce IS the collective — exactly one launch, and
            # nothing for it to overlap with inside its own program
            "expected_collectives": {"all-reduce": 1},
            "resharding_free": True,
        },
        meta={"shape": list(shape), "dtype": "float32", "devices": 8})


@_entrypoint("allreduce.bucket_2bit")
def _capture_allreduce_2bit():
    """The compressed bucket reduce: int8 levels ride the ring, each
    device rescales its own shard — the narrow dtype must SURVIVE into
    the collective (EQuARX-style), which the dtype census locks."""
    import jax
    import jax.numpy as jnp
    import numpy as onp

    from mxnet_tpu.kvstore.tpu_ici import _compressed_allreduce_fn

    devices = _ici_devices()
    shape = (16384,)
    allreduce, sharding, _mesh = _compressed_allreduce_fn(
        devices, shape, onp.dtype(onp.float32), 0.01)
    spec = jax.ShapeDtypeStruct((len(devices),) + shape, jnp.int8,
                                sharding=sharding)
    return _capture_jit(
        allreduce, (spec,), "allreduce.bucket_2bit", "allreduce",
        contract={
            "expected_collectives": {"all-reduce": 1},
            "resharding_free": True,
        },
        meta={"shape": list(shape), "dtype": "int8->float32",
              "threshold": 0.01, "devices": 8})


def _capture_allreduce_blockwise(qtype):
    """Shared capture for the block-scaled quantized bucket reduce: the
    fused quantize -> pmax(scale) -> psum(payload) -> dequantize program
    from `_blockwise_allreduce_fn`, taking the stacked gradient AND
    residual shards.  TWO all-reduce ops in the HLO is the honest,
    pinned census: the ~1/256-sized scale-agreement pmax and the
    widened narrow-payload psum both live in ONE compiled launch."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.kvstore.tpu_ici import (DEFAULT_QBLOCK,
                                           _blockwise_allreduce_fn)

    devices = _ici_devices()
    numel = 16384
    allreduce, sharding, _mesh = _blockwise_allreduce_fn(
        devices, numel, "float32", qtype, DEFAULT_QBLOCK)
    spec = jax.ShapeDtypeStruct((len(devices), numel), jnp.float32,
                                sharding=sharding)
    # the third operand is the (n_dev, 1) launch-chain token that orders
    # consecutive blockwise launches without a host fence — pure
    # scheduling, no collective of its own
    tok_spec = jax.ShapeDtypeStruct((len(devices), 1), jnp.float32,
                                    sharding=sharding)
    wire = "int8->int16" if qtype == "int8" else "float8_e4m3->bfloat16"
    return _capture_jit(
        allreduce, (spec, spec, tok_spec), f"allreduce.bucket_{qtype}",
        "allreduce",
        contract={
            # pmax (scale agreement) + psum (payload): both collectives
            # of the fused program, still one launch per bucket
            "expected_collectives": {"all-reduce": 2},
            "resharding_free": True,
        },
        meta={"numel": numel, "dtype": f"float32->{wire}",
              "block": DEFAULT_QBLOCK, "devices": 8})


@_entrypoint("allreduce.bucket_dense_integrity")
def _capture_allreduce_dense_integrity():
    """The DECLARED integrity-mode variant of `allreduce.bucket_dense`
    (``MXNET_KVSTORE_INTEGRITY=1``): the same bucket psum plus the
    in-program digest sideband — a pmax over the packed ``[d, -d]``
    digest pair (max and min agreement in ONE collective) riding the
    SAME launch.  Pinned at 2 all-reduce ops so integrity mode is a
    contract variant, not a launch-count violation; the default dense
    contract above stays at 1."""
    import jax
    import jax.numpy as jnp
    import numpy as onp

    from mxnet_tpu.kvstore.tpu_ici import _allreduce_fn

    devices = _ici_devices()
    shape = (16384,)
    allreduce, sharding, _mesh = _allreduce_fn(
        devices, shape, onp.dtype(onp.float32), True)
    spec = jax.ShapeDtypeStruct((len(devices),) + shape, jnp.float32,
                                sharding=sharding)
    flip = jax.ShapeDtypeStruct((len(devices), 1), jnp.float32,
                                sharding=sharding)
    return _capture_jit(
        allreduce, (spec, flip), "allreduce.bucket_dense_integrity",
        "allreduce",
        contract={
            # payload psum + digest-agreement pmax, one launch
            "expected_collectives": {"all-reduce": 2},
            "resharding_free": True,
        },
        meta={"shape": list(shape), "dtype": "float32", "devices": 8,
              "mode": "integrity"})


@_entrypoint("allreduce.bucket_int8_integrity")
def _capture_allreduce_int8_integrity():
    """The DECLARED integrity-mode variant of `allreduce.bucket_int8`:
    scale-agreement pmax + payload psum + digest-agreement pmax, all in
    the one fused launch — 3 all-reduce ops pinned (the default
    blockwise contract stays at 2)."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.kvstore.tpu_ici import (DEFAULT_QBLOCK,
                                           _blockwise_allreduce_fn)

    devices = _ici_devices()
    numel = 16384
    allreduce, sharding, _mesh = _blockwise_allreduce_fn(
        devices, numel, "float32", "int8", DEFAULT_QBLOCK, True)
    spec = jax.ShapeDtypeStruct((len(devices), numel), jnp.float32,
                                sharding=sharding)
    tok_spec = jax.ShapeDtypeStruct((len(devices), 1), jnp.float32,
                                    sharding=sharding)
    return _capture_jit(
        allreduce, (spec, spec, tok_spec, tok_spec),
        "allreduce.bucket_int8_integrity", "allreduce",
        contract={
            # pmax (scales) + psum (payload) + pmax (digest), one launch
            "expected_collectives": {"all-reduce": 3},
            "resharding_free": True,
        },
        meta={"numel": numel, "dtype": "float32->int8->int16",
              "block": DEFAULT_QBLOCK, "devices": 8,
              "mode": "integrity"})


@_entrypoint("allreduce.bucket_int8")
def _capture_allreduce_int8():
    """Block-scaled int8 bucket reduce (see
    `_capture_allreduce_blockwise`): int8 payload, int16 accumulator."""
    return _capture_allreduce_blockwise("int8")


@_entrypoint("allreduce.bucket_fp8")
def _capture_allreduce_fp8():
    """Block-scaled fp8 bucket reduce (see
    `_capture_allreduce_blockwise`): float8_e4m3 payload, bfloat16
    accumulator."""
    return _capture_allreduce_blockwise("fp8")


class _PlanVal:
    """Shape/dtype stand-in for a gradient copy: exactly what
    GradBucketer's planner reads (``._data.dtype``, ``.shape``,
    ``.size``; `_value_devices` sees a non-jax ``.data`` and records
    host placement), so the REAL planner produces the plan without
    materializing 3.75 MB of fake gradients."""

    def __init__(self, shape, dtype):
        import jax

        self._data = jax.ShapeDtypeStruct(tuple(shape), dtype)
        self.data = self._data
        self.shape = tuple(shape)
        self.size = 1
        for d in shape:
            self.size *= int(d)


def bucketed_step_plan(bucket_bytes=BUCKETED_STEP_BUCKET_BYTES):
    """The GradBucketer plan for the resnet50 profile: list of bucket
    capacities (elements).  This is the planner the trainer runs, fed
    the benchmark's canonical gradient profile."""
    import jax.numpy as jnp

    from mxnet_tpu.kvstore.bucketing import GradBucketer

    items = [(f"g{i}", [_PlanVal((n,), jnp.float32)])
             for i, n in enumerate(RESNET50_PROFILE)]
    bucketer = GradBucketer(bucket_bytes=bucket_bytes)
    plan = bucketer._build_plan(items)
    return [b.capacity for b in plan]


@_entrypoint("allreduce.bucketed_step")
def _capture_bucketed_step():
    """One step's worth of bucketed gradient collectives as a single
    module: the resnet50 profile planned by the real GradBucketer, one
    shard_map psum per bucket.  launch-count on this artifact is the
    compiled-side lock on PR 4's 160 -> 4 collapse: if the planner (or
    a bucketer bypass) changes the bucket count, the census moves and
    the scan fails."""
    import jax
    import jax.numpy as jnp
    import numpy as onp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    capacities = bucketed_step_plan()
    devices = tuple(jax.local_devices()[:8])
    mesh = Mesh(onp.asarray(devices), ("dev",))
    sharding = NamedSharding(mesh, P("dev"))

    def step(*bufs):
        return tuple(jax.lax.psum(b, "dev") for b in bufs)

    reduce_all = jax.shard_map(step, mesh=mesh,
                               in_specs=(P("dev"),) * len(capacities),
                               out_specs=(P("dev"),) * len(capacities))
    jitted = jax.jit(reduce_all,
                     in_shardings=(sharding,) * len(capacities),
                     out_shardings=(sharding,) * len(capacities))
    specs = tuple(
        jax.ShapeDtypeStruct((len(devices), cap), jnp.float32,
                             sharding=sharding)
        for cap in capacities)
    return _capture_jit(
        jitted, specs, "allreduce.bucketed_step", "allreduce",
        contract={
            # one psum per bucket, as the program spells them: counted
            # before XLA's combiner, whose merging is not the plan's
            "expected_collectives": {"all-reduce": len(capacities)},
            "collectives_stage": "lowered",
            "resharding_free": True,
        },
        meta={"profile": "resnet50",
              "n_tensors": len(RESNET50_PROFILE),
              "n_buckets": len(capacities),
              "bucket_bytes": BUCKETED_STEP_BUCKET_BYTES,
              "capacities": capacities})


@_entrypoint("allreduce.bucketed_step_int8")
def _capture_bucketed_step_int8():
    """The quantized twin of `allreduce.bucketed_step`: the SAME
    GradBucketer plan over the resnet50 profile, but each bucket runs
    the real `_blockwise_shard_body` int8 math instead of a bare psum.
    The census pins 2 all-reduce ops per bucket (scale pmax + payload
    psum) while the *launch* count the trainer sees stays one per
    bucket — still 4 for the 160-tensor profile, which the dryrun
    `dp_collective_launches_per_step` rider measures at runtime."""
    import jax
    import jax.numpy as jnp
    import numpy as onp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from mxnet_tpu.kvstore.tpu_ici import (DEFAULT_QBLOCK,
                                           _blockwise_shard_body)

    capacities = bucketed_step_plan()
    devices = tuple(jax.local_devices()[:8])
    mesh = Mesh(onp.asarray(devices), ("dev",))
    sharding = NamedSharding(mesh, P("dev"))
    bodies = [_blockwise_shard_body(cap, onp.dtype(onp.float32), "int8",
                                    DEFAULT_QBLOCK, len(devices))
              for cap in capacities]

    def step(*bufs):
        # bufs = grads then residuals (one of each per bucket), then the
        # launch-chain token, threaded bucket to bucket exactly as the
        # runtime chains consecutive launches
        n = len(capacities)
        tok = bufs[2 * n]
        flat = []
        for body, g, r in zip(bodies, bufs[:n], bufs[n:2 * n]):
            out, new_res, tok = body(g, r, tok)
            flat += [out, new_res]
        return tuple(flat) + (tok,)

    n_arg = 2 * len(capacities) + 1
    reduce_all = jax.shard_map(step, mesh=mesh,
                               in_specs=(P("dev"),) * n_arg,
                               out_specs=(P("dev"),) * n_arg)
    jitted = jax.jit(
        reduce_all,
        in_shardings=(sharding,) * n_arg,
        out_shardings=(sharding,) * n_arg)
    specs = tuple(
        jax.ShapeDtypeStruct((len(devices), cap), jnp.float32,
                             sharding=sharding)
        for cap in capacities) * 2 + (
        jax.ShapeDtypeStruct((len(devices), 1), jnp.float32,
                             sharding=sharding),)
    return _capture_jit(
        jitted, specs, "allreduce.bucketed_step_int8", "allreduce",
        contract={
            "expected_collectives": {"all-reduce": 2 * len(capacities)},
            "collectives_stage": "lowered",
            "resharding_free": True,
        },
        meta={"profile": "resnet50",
              "n_tensors": len(RESNET50_PROFILE),
              "n_buckets": len(capacities),
              "bucket_bytes": BUCKETED_STEP_BUCKET_BYTES,
              "block": DEFAULT_QBLOCK,
              "mode": "int8",
              "capacities": capacities})


# --------------------------------------------------------------------------
# flash attention
# --------------------------------------------------------------------------
def _flash_fn():
    import functools

    from mxnet_tpu.ops.pallas_kernels import flash_attention

    # interpret mode: the kernel lowers to plain HLO on CPU — the same
    # program structure (blocked streaming, masks) without Mosaic
    return functools.partial(flash_attention, causal=True, interpret=True)


def _flash_specs():
    import jax
    import jax.numpy as jnp

    shape = (1, 2, 16, 8)   # (B, H, T, D): tiny — capture, not perf
    return tuple(jax.ShapeDtypeStruct(shape, jnp.bfloat16)
                 for _ in range(3))


@_entrypoint("flash_attention.fwd")
def _capture_flash_fwd():
    import jax

    fa = _flash_fn()
    jitted = jax.jit(lambda q, k, v: fa(q, k, v))
    return _capture_jit(
        jitted, _flash_specs(), "flash_attention.fwd", "kernel",
        contract=_flash_contract(),
        meta={"shape": [1, 2, 16, 8], "dtype": "bfloat16",
              "causal": True, "mode": "interpret"})


@_entrypoint("flash_attention.bwd")
def _capture_flash_bwd():
    import jax
    import jax.numpy as jnp

    fa = _flash_fn()

    def loss(q, k, v):
        return jnp.sum(fa(q, k, v).astype(jnp.float32))

    jitted = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
    return _capture_jit(
        jitted, _flash_specs(), "flash_attention.bwd", "kernel",
        contract=_flash_contract(),
        meta={"shape": [1, 2, 16, 8], "dtype": "bfloat16",
              "causal": True, "mode": "interpret"})


def _flash_contract():
    return {
        "dtype_policy": "bf16",
        "collective_free": True,
        "resharding_free": True,
        "waivers": [
            {"rule": "dtype-cliff",
             "reason": "flash softmax accumulates scores/log-sum-exp in "
                       "f32 by design (the kernel's documented numerics: "
                       "bf16 operands, f32 running max/denominator) — "
                       "the f32 island is the NaN fence, not a leak"},
        ],
    }


# --------------------------------------------------------------------------
# serve endpoint
# --------------------------------------------------------------------------
@_entrypoint("serve.endpoint")
def _capture_serve_endpoint():
    """The serve Endpoint's cached executable for one bucket: the very
    program traffic runs through (ExecutableCache.hlo_texts), not a
    re-lowering.  Single-device serving must stay collective- and
    host-callback-free."""
    import numpy as onp

    import mxnet_tpu as mx

    net = mx.gluon.nn.Dense(8, in_units=16)
    net.initialize()
    ep = mx.serve.Endpoint(net, max_batch_size=4, batch_buckets=[4],
                           start=False)
    x = onp.zeros((4, 16), onp.float32)
    ep._ensure_executable([x])
    ep._cache.warm([((4, 16), onp.float32)])
    texts = ep._cache.hlo_texts()
    sig, opt = sorted(texts.items())[0]
    return {
        "name": "serve.endpoint", "kind": "serve",
        "jaxpr": None, "lowered": None, "optimized": opt,
        "contract": {
            "collective_free": True,
            "resharding_free": True,
        },
        "meta": {"signature": sig, "entries": len(texts)},
    }


# --------------------------------------------------------------------------
# driver API
# --------------------------------------------------------------------------
def capture_one(name):
    _ensure_virtual_mesh()
    try:
        fn = _ENTRYPOINTS[name]
    except KeyError:
        raise KeyError(
            f"unknown artifact {name!r}; known: {entrypoint_names()}") \
            from None
    return fn()


def capture_all(names=None):
    """Capture specs for ``names`` (default: every entry point)."""
    _ensure_virtual_mesh()
    names = entrypoint_names() if not names else list(names)
    return [capture_one(n) for n in names]
