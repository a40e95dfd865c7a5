#!/usr/bin/env bash
# Pre-commit smoke gate (VERDICT r1 "Next round" #1): never ship a snapshot
# that cannot import or train a step.  Run from repo root:
#   bash tools/smoke.sh
set -euo pipefail
cd "$(dirname "$0")/.."

export JAX_PLATFORMS=cpu
export XLA_FLAGS="${XLA_FLAGS:-} --xla_force_host_platform_device_count=8"

# 0. import gate (ISSUE 1): a bare import must succeed and the test tree
# must collect with ZERO errors — an import-time crash (like the jax
# shard_map move that broke the seed) can never land again.
python -c "import mxnet_tpu; print('smoke: import ok')"
collect_log=$(mktemp)
if ! python -m pytest tests/ -q --collect-only -p no:cacheprovider \
    > "$collect_log" 2>&1; then
  echo "smoke: FAIL — test collection errored:" >&2
  grep -E "ERROR|error" "$collect_log" | head -20 >&2
  rm -f "$collect_log"
  exit 1
fi
if grep -qE "[0-9]+ errors?" "$collect_log"; then
  echo "smoke: FAIL — collection reported errors:" >&2
  tail -5 "$collect_log" >&2
  rm -f "$collect_log"
  exit 1
fi
rm -f "$collect_log"
echo "smoke: collect-only 0 errors"

# 0b. quick concurrency-contract gate (ISSUE 20): the interprocedural
# lock-order / blocking-under-lock scan is pure-AST (no package import)
# and must stay clean against the EMPTY committed baseline — a new lock
# ordering or a blocking call slipped under a lock can never land
python -m tools.lockscan --verdicts --no-metrics
echo "smoke: lockscan concurrency contracts ok"

python - <<'EOF'
import mxnet_tpu as mx
import numpy as onp

# 1. import + one tiny train step through the Gluon path
net = mx.gluon.nn.Dense(4)
net.initialize()
trainer = mx.gluon.Trainer(net.collect_params(), "sgd", {"learning_rate": 0.1})
x = mx.np.array(onp.random.randn(2, 3).astype(onp.float32))
with mx.autograd.record():
    loss = (net(x) ** 2).mean()
loss.backward()
trainer.step(2)
assert onp.isfinite(loss.asnumpy()).all()
print("smoke: train step ok")

# 1b. resilience gate (ISSUE 9): the full-state checkpoint round-trip —
# a snapshot of the trainer we just stepped must commit atomically and
# restore bitwise into a FRESH net+trainer (docs/RESILIENCE.md)
import tempfile
from mxnet_tpu.resilience import (CheckpointManager, gather_training_state,
                                  restore_training_state)
with tempfile.TemporaryDirectory() as _root:
    with CheckpointManager(_root, async_write=False, rank=0) as _mgr:
        _arrays, _meta = gather_training_state(trainer, step=1)
        _mgr.save(1, _arrays, _meta)
        _net2 = mx.gluon.nn.Dense(4)
        _net2.initialize()
        _net2(x)  # materialize deferred shapes
        _tr2 = mx.gluon.Trainer(_net2.collect_params(), "sgd",
                                {"learning_rate": 0.1})
        _step, _arrays_r, _meta_r = _mgr.restore_latest()
        assert _step == 1, _step
        restore_training_state(_arrays_r, _meta_r, _tr2)
        for _p, _q in zip(trainer._params, _tr2._params):
            assert _p.data().asnumpy().tobytes() == \
                _q.data().asnumpy().tobytes(), _p.name
print("smoke: checkpoint round-trip ok")

# 2. the serving subsystem answers one request end to end
ep = mx.serve.Endpoint(net, max_batch_size=4, max_latency_ms=2)
out = ep.predict(x)
assert out.shape == (2, 4)
assert ep.stats()["completed"] == 1
ep.shutdown(drain=True)
print("smoke: serve round-trip ok")

# 2a'. fleet failover gate (ISSUE 12): 2 replicas, a faultline plan
# kills one at its first dispatch, and the request must complete on the
# survivor with the recovery visible in mxtpu_faults_recovered_total —
# the quick round-trip version of the ci.sh storm stage
from mxnet_tpu import telemetry as _tel
from mxnet_tpu.resilience import faultline as _fl
_fl.clear()
_fl.plan([{"site": "serve.replica", "kind": "preempt", "at": 1}])
_fleet = mx.serve.Fleet(net, replicas=2, name="smoke_fleet",
                        max_batch_size=4, max_latency_ms=2)
_fout = _fleet.predict(x, cls="interactive", timeout_ms=60000)
assert _fout.shape == (2, 4)
_fl.clear()
_dead = [r.index for r in _fleet.replicas if r.state == "dead"]
assert len(_dead) == 1, _fleet.describe_state()
_frec = _tel.default_registry().get_sample_value(
    "mxtpu_faults_recovered_total",
    {"site": "serve.replica", "kind": "preempt"})
assert _frec and _frec >= 1, _frec
_fleet.shutdown(drain=True)
print(f"smoke: fleet failover ok (r{_dead[0]} killed, survivor answered)")

# 2b. telemetry gate (ISSUE 2): the Prometheus exposition must parse and
# reflect the traffic just served — a broken exporter or a silently
# non-publishing endpoint can never land
import re as _re
from mxnet_tpu import telemetry
text = telemetry.export_prometheus()
line_re = _re.compile(
    r'^(# (HELP|TYPE) [a-zA-Z_:][a-zA-Z0-9_:]* ?.*'
    r'|[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^{}]*\})? [-+0-9.eE]+)$')
for line in text.splitlines():
    if line:
        assert line_re.match(line), f"unparseable exposition line: {line!r}"
completed = telemetry.default_registry().get_sample_value(
    "mxtpu_serve_requests_total", {"endpoint": ep.name, "event": "completed"})
assert completed and completed >= 1, f"serve counter not published: {completed}"
assert "mxtpu_trainer_step_phase_seconds" in text  # trainer series present
print("smoke: telemetry export ok")

# 2c. bucketed allreduce gate (ISSUE 4): a multi-copy trainer step must
# collapse gradient collectives below one-per-parameter — if this fires,
# bucketing silently disengaged and every step pays per-key launches
ctxs = [mx.cpu(i) for i in range(4)]
net2 = mx.gluon.nn.HybridSequential()
net2.add(mx.gluon.nn.Dense(8, in_units=6))
net2.add(mx.gluon.nn.Dense(8, in_units=8))
net2.add(mx.gluon.nn.Dense(4, in_units=8))
net2.initialize(ctx=ctxs)
tr2 = mx.gluon.Trainer(net2.collect_params(), "sgd",
                       {"learning_rate": 0.1}, kvstore="tpu_ici")
from mxnet_tpu import autograd as _ag
from mxnet_tpu.gluon.utils import split_and_load as _sal

def _dp_step():
    xs = _sal(mx.np.array(onp.random.randn(8, 6).astype(onp.float32)), ctxs)
    with _ag.record():
        ls = [(net2(xb) ** 2).mean() for xb in xs]
    _ag.backward(ls)
    tr2.step(8)

_dp_step()  # kv init + broadcast + first-step traces
_reg = telemetry.default_registry()
_launch_name = "mxtpu_kvstore_collective_launches_total"
_before = _reg.get_sample_value(_launch_name) or 0.0
_dp_step()
_delta = (_reg.get_sample_value(_launch_name) or 0.0) - _before
_n_params = len([k for k in net2.collect_params()])
assert _n_params == 6 and _delta < _n_params, (_delta, _n_params)
print(f"smoke: bucketed allreduce ok ({int(_delta)} launches for "
      f"{_n_params} params)")

# 2c'. block-scaled quantized allreduce gate (ISSUE 11): the int8 path
# must keep every copy bitwise in sync, reproduce bitwise across fresh
# stores (integer psum is reduction-order-free), and land within the
# block-scale rounding envelope of the dense sum
from mxnet_tpu import kvstore as _kvs

_QN, _QBLK = 128, 64
_qxs = [(onp.random.RandomState(5).randn(_QN) * (c + 1)).astype(onp.float32)
        for c in range(4)]

def _int8_reduce():
    _kv = _kvs.create("tpu_ici")
    _kv.set_gradient_compression({"type": "int8", "block": _QBLK})
    _vals = [mx.np.array(_x, ctx=mx.cpu(c)) for c, _x in enumerate(_qxs)]
    _kv.pushpull(0, _vals)
    return [_v.asnumpy() for _v in _vals]

_q1, _q2 = _int8_reduce(), _int8_reduce()
assert all(onp.array_equal(_q1[0], _c) for _c in _q1[1:]), \
    "int8 reduce left device copies out of sync"
assert all(onp.array_equal(_a, _b) for _a, _b in zip(_q1, _q2)), \
    "int8 reduce must be run-to-run deterministic"
# shared per-block scale = pmax(amax)/127; each copy rounds once, so
# |quantized sum - dense sum| <= n_copies * scale / 2 per element
_qdense = sum(_qxs)
_scale = onp.max(onp.abs(onp.stack(_qxs)).reshape(4, -1, _QBLK),
                 axis=(0, 2)) / 127.0
_qerr = onp.abs(_q1[0] - _qdense).reshape(-1, _QBLK)
assert (_qerr <= len(_qxs) * _scale[:, None] / 2 + 1e-6).all(), \
    "int8 reduce outside the block-scale rounding envelope"
print("smoke: block-scaled int8 allreduce parity ok")

# 2d. input-pipeline gate (ISSUE 10): sharded readers must partition the
# record file deterministically, and the sharded prefetcher must build dp
# global batches accounted under kind=shard_put (one wire crossing, no
# host-side replication)
import io as _pio
import os as _os
import tempfile as _tf
from PIL import Image as _Image
from mxnet_tpu import parallel as _par
from mxnet_tpu import recordio as _rio
from mxnet_tpu.io import DevicePrefetcher as _DPF, ImageRecordIter as _IRI

_tmpd = _tf.mkdtemp()
_rec = _os.path.join(_tmpd, "smoke.rec")
_w = _rio.MXRecordIO(_rec, "w")
_rs = onp.random.RandomState(0)
for _i in range(16):
    _b = _pio.BytesIO()
    _Image.fromarray(_rs.randint(0, 255, (16, 16, 3), dtype=onp.uint8)
                     ).save(_b, "JPEG")
    _w.write(_rio.pack(_rio.IRHeader(0, float(_i), _i, 0), _b.getvalue()))
_w.close()

def _part_labels(part):
    _it = _IRI(_rec, batch_size=4, data_shape=(3, 16, 16), shuffle=True,
               seed=3, num_parts=2, part_index=part, preprocess_threads=2)
    _out = []
    for _ in range(2):
        _, _lab = _it.next_arrays()
        _out.extend(int(_v) for _v in _lab)
    _it.close()
    return _out

_p0, _p1 = _part_labels(0), _part_labels(1)
assert _p0 == _part_labels(0), "sharded reader order must be deterministic"
assert sorted(_p0 + _p1) == list(range(16)), "parts must partition exactly"

_mesh = _par.make_mesh({"dp": -1})
_sh = _par.data_sharding(_mesh)
_it = _IRI(_rec, batch_size=8, data_shape=(3, 16, 16), shuffle=True, seed=3)
_spb = telemetry.default_registry().get_sample_value(
    "mxtpu_mesh_transfer_bytes_total", {"kind": "shard_put"}) or 0.0
with _DPF(_it, sharding=_sh, dtypes=(None, onp.int32)) as _pf:
    _xb, _yb = next(_pf)
assert _xb._data.sharding.is_equivalent_to(_sh, 4), _xb._data.sharding
_spa = telemetry.default_registry().get_sample_value(
    "mxtpu_mesh_transfer_bytes_total", {"kind": "shard_put"}) or 0.0
assert _spa > _spb, "sharded feed must account bytes under kind=shard_put"
_it.close()
print("smoke: input pipeline ok (sharded readers + dp global feed)")

# 2e. flaky-kv retry-storm gate (ISSUE 14): a burst of intermittent
# ConnectionErrors at the pushpull site must be absorbed by the
# per-rank-jittered bounded-backoff retry policy — every pushpull
# completes, the storm is visible in mxtpu_kvstore_retries_total, and
# the recoveries are booked under kind="flaky" (not "timeout") — all
# inside a 10 s wall budget
import time as _time
_fl.clear()
_fl.plan([{"site": "kvstore.pushpull", "kind": "flaky",
           "at": 3 * _k + 1, "times": 2, "seed": _k} for _k in range(6)])
_ret_b = _reg.get_sample_value(
    "mxtpu_kvstore_retries_total", {"site": "kvstore.pushpull"}) or 0.0
_rec_b = _reg.get_sample_value(
    "mxtpu_faults_recovered_total",
    {"site": "kvstore.pushpull", "kind": "flaky"}) or 0.0
_skv = _kvs.create("tpu_ici")
_sval = mx.np.array(onp.ones(8, dtype=onp.float32))
_t0 = _time.monotonic()
for _i in range(12):
    _skv.pushpull(_i, _sval)
_storm_wall = _time.monotonic() - _t0
_fl.clear()
_ret_d = (_reg.get_sample_value(
    "mxtpu_kvstore_retries_total", {"site": "kvstore.pushpull"}) or 0.0
    ) - _ret_b
_rec_d = (_reg.get_sample_value(
    "mxtpu_faults_recovered_total",
    {"site": "kvstore.pushpull", "kind": "flaky"}) or 0.0) - _rec_b
assert _ret_d >= 1, "flaky storm produced no retries"
assert _rec_d >= 1, "recoveries not booked under kind=flaky"
assert _storm_wall < 10.0, f"retry storm blew the wall budget: {_storm_wall}"
print(f"smoke: flaky-kv retry storm ok ({int(_ret_d)} retries, "
      f"{int(_rec_d)} flaky recoveries, {_storm_wall:.1f}s)")
EOF

# 3b. quick compiled-program contract gate (ISSUE 7): the cheap
# allreduce artifacts only — bucket census + resharding-freedom at the
# HLO level; the full artifact set runs in ci.sh's hloscan stage.  The
# block-scaled programs (ISSUE 11) are pinned here too: quantize +
# scale-agreement pmax + payload psum + dequantize must stay ONE launch
# per bucket (2 all-reduce ops, zero extra dispatches).  The integrity
# variants (ISSUE 14) are pinned too: the digest-agreement sideband must
# cost exactly one extra collective in the SAME program, never a second
# launch
python -m tools.hloscan allreduce.bucket_dense allreduce.bucket_2bit \
  allreduce.bucket_int8 allreduce.bucket_fp8 \
  allreduce.bucket_dense_integrity allreduce.bucket_int8_integrity \
  allreduce.bucketed_step allreduce.bucketed_step_int8 \
  --verdicts --no-metrics
echo "smoke: hloscan allreduce contracts ok"

# 3c. layer-census gate (ISSUE 8): the dp FusedTrainStep census artifact
# must parse and attribute nonzero FLOPs to named Gluon layers — a
# silently-empty census (name scopes stripped, metadata lost) can never
# land.  The full contract gate runs in ci.sh's census stage.
python - <<'EOF'
import json
from tools.layerscope import driver as layerscope

docs = layerscope.census_docs(["fused_train_step_dp"])
path = layerscope.write_artifact(docs[0])
doc = json.loads(open(path).read())
assert doc["schema"] == "mxtpu-layer-census-v1", doc.get("schema")
named = sum(r["flops"] for r in doc["rows"]
            if r["layer"] != "(unattributed)")
assert named > 0, "census attributed zero FLOPs to named layers"
assert doc["attributed_flops_fraction"] >= 0.9, \
    doc["attributed_flops_fraction"]
print(f"smoke: layer census ok ({doc['attributed_flops_fraction']:.1%} "
      f"of {doc['totals']['flops']:.0f} FLOPs attributed)")
EOF

# 3d. sharding-recipe parity gate (ISSUE 16): a dp2.tp2 recipe-built
# fused step must match the dp-only oracle's 3-step loss trajectory
# bitwise at the same global batch — sharding annotations never change
# numerics, so ANY drift means the recipe subsystem broke placement or
# rule collection.  The full recipe rider (3D step + hloscan contract +
# giant-model placement) runs in ci.sh's dryrun stage.
python - <<'EOF'
import numpy as onp
import mxnet_tpu.random as _rng
from mxnet_tpu.analysis.capture import (build_dp_fused_step,
                                        build_recipe_fused_step)

def run3(builder):
    _rng.seed(0)
    fused, (x, y), bs, _meta = builder()
    return [onp.asarray(fused(x, y, batch_size=bs)._data).sum()
            for _ in range(3)]

dp, tp = run3(build_dp_fused_step), run3(build_recipe_fused_step)
assert dp == tp, f"recipe dp2.tp2 diverged from the dp oracle: {dp} vs {tp}"
print(f"smoke: recipe dp2.tp2 parity ok (3-step losses {tp})")
EOF

# 4. the driver entry points compile on the virtual mesh (the full
# hloscan + census + recipe dryrun riders run in ci.sh's
# dryrun stage, not here — 3d above covers the quick checks)
MXTPU_DRYRUN_HLOSCAN=0 MXTPU_DRYRUN_CENSUS=0 MXTPU_DRYRUN_RESILIENCE=0 \
  MXTPU_DRYRUN_FLEET=0 MXTPU_DRYRUN_GRAY=0 MXTPU_DRYRUN_RECIPE=0 \
  MXTPU_DRYRUN_LOCKSCAN=0 \
  python -c "
import __graft_entry__ as g
g.dryrun_multichip(8)
print('smoke: dryrun_multichip(8) ok')
"
echo "SMOKE PASS"
