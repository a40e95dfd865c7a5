#!/usr/bin/env bash
# CI entry (reference: ci/build.py + runtime_functions.sh stages).
# Stages: lint | lockscan | import | hloscan | census | smoke | test |
# chaos | storm | endure | blackbox | perf | dryrun | all
# (default: all).
set -euo pipefail
cd "$(dirname "$0")/.."
stage="${1:-all}"

export JAX_PLATFORMS=cpu
export XLA_FLAGS="${XLA_FLAGS:-} --xla_force_host_platform_device_count=8"

run_lint() {
  # zero-unbaselined-findings gate (ISSUE 5): pure-AST, runs before
  # anything imports — trace-time env reads, lock discipline, host
  # syncs in jit, daemon-thread leaks, undocumented MXNET_* knobs
  # (docs/STATIC_ANALYSIS.md; waive with `# mxlint: disable=<rule> --
  # <reason>`, grandfather with --update-baseline)
  python -m tools.mxlint
}
run_lockscan() {
  # concurrency-contract gate (ISSUE 20): interprocedural lock-order /
  # blocking-under-lock analysis over the package — lock-order cycles,
  # blocking calls under held locks, predicate-free Condition.wait,
  # notify outside the owning lock, lock-taking signal handlers — clean
  # against the EMPTY committed baseline (docs/STATIC_ANALYSIS.md
  # "Concurrency contracts"; waive with `# lockscan: disable=<rule> --
  # <reason>`).  The runtime half (the acquisition witness) rides the
  # chaos/storm/endure stages below via MXNET_LOCKSCAN_WITNESS.
  python -m tools.lockscan --verdicts
}
run_import() {
  # hard gate (ISSUE 1): bare import + zero collection errors, so an
  # import-time crash can never land again
  python -c "import mxnet_tpu; print('ci: import ok')"
  out=$(python -m pytest tests/ -q --collect-only -p no:cacheprovider \
        2>&1 | tail -3)
  if echo "$out" | grep -qE "[0-9]+ errors?"; then
    echo "ci: FAIL — collection errors:" >&2
    echo "$out" >&2
    exit 1
  fi
  echo "ci: collect-only 0 errors"
}
run_hloscan() {
  # compiled-program contract gate (ISSUE 7): captures the real entry
  # points (train step on the virtual mesh, bucketed allreduce, flash
  # attention, serve endpoint) and checks their jaxprs + HLO against the
  # declared contracts — collective overlap, host round-trips, dtype
  # cliffs, resharding, launch counts (docs/STATIC_ANALYSIS.md; waive in
  # the artifact's contract, grandfather with --update-baseline)
  python -m tools.hloscan --verdicts
}
run_census() {
  # per-layer speed-of-light census gate (ISSUE 8): attributes each
  # captured entry point's compiled FLOPs/bytes to Gluon layers and
  # fences them with MFU-floor contracts — cost-model-only on the CPU
  # mesh (docs/OBSERVABILITY.md "Layer census"; waive on the contract
  # with a reason, grandfather with --update-baseline)
  python -m tools.layerscope --verdicts
}
run_smoke()  { bash tools/smoke.sh; }
run_test()   {
  # masked/dropout flash parity first (ISSUE 3): the kernel tier BERT
  # training rides must fail fast and loud before anything else runs
  python -m pytest tests/test_flash_attention.py -q
  # the three static-analysis gates' own suites next (ISSUEs 5+7+20): a
  # broken checker is worse than no checker
  python -m pytest tests/test_mxlint.py tests/test_hloscan.py \
    tests/test_lockscan.py -q
  # telemetry next: the observability layer every later perf PR reads
  # its numbers from fails fast and loud (ISSUE 2)
  python -m pytest tests/test_telemetry.py -q
  # bucketed collectives (ISSUE 4): the allreduce path every multi-device
  # trainer step rides — bit-parity vs per-key must fail fast
  python -m pytest tests/test_kvstore_bucketing.py -q
  # input pipeline (ISSUE 10): sharded readers, device augment, and the
  # sharded global-array feed — the path every real-data bench rides
  python -m pytest tests/test_image_record.py tests/test_input_pipeline.py -q
  python -m pytest tests/ -q -x
}
run_chaos()  {
  # runtime lock-acquisition witness (ISSUE 20): every process in this
  # gate (and storm/endure below) runs with the lockwitness factory shim
  # installed — an out-of-order acquisition aborts that process with
  # exit 70 and fails the stage; the env-plan run additionally dumps its
  # observed acquisition graph and crosschecks it against the static
  # model (MXNET_LOCKSCAN_WITNESS=0 opts out)
  export MXNET_LOCKSCAN_WITNESS="${MXNET_LOCKSCAN_WITNESS:-1}"
  # chaos gate (ISSUE 9): deterministic fault injection + recovery — the
  # resume-parity fence, the retry/step-guard policies, and the atomic
  # checkpoint round-trip must all survive without process death
  # (docs/RESILIENCE.md)
  python -m pytest tests/test_resilience.py -q
  # whole-process path: a fault plan injected via MXNET_FAULTLINE (not
  # plan()) must fire in a fresh interpreter and be retried away, visible
  # in mxtpu_faults_recovered_total
  MXNET_FAULTLINE='[{"site": "kvstore.pushpull", "kind": "timeout", "at": 1}]' \
  MXNET_LOCKSCAN_REPORT="/tmp/lockscan-chaos-$$.json" \
  python - <<'EOF'
import numpy as onp
import mxnet_tpu as mx
from mxnet_tpu import kvstore, telemetry

kv = kvstore.create("tpu_ici")
vals = [mx.np.array(onp.array([1.0, 2.0], onp.float32), ctx=mx.cpu(c))
        for c in range(2)]
kv.pushpull("k", vals)
assert vals[0].asnumpy().tolist() == [2.0, 4.0]
rec = telemetry.default_registry().get_sample_value(
    "mxtpu_faults_recovered_total",
    {"site": "kvstore.pushpull", "kind": "timeout"})
assert rec == 1, rec
print("ci: env-plan KV timeout injected and recovered")
EOF
  # the witness run above dumped its observed acquisition graph — the
  # merged static+observed order must be acyclic and every observed edge
  # explained by the static model (ISSUE 20)
  if [ "${MXNET_LOCKSCAN_WITNESS}" != "0" ] && \
     [ -f "/tmp/lockscan-chaos-$$.json" ]; then
    python -m tools.lockscan --no-metrics \
      --crosscheck "/tmp/lockscan-chaos-$$.json"
    rm -f "/tmp/lockscan-chaos-$$.json"
  fi
  # quantized preempt/resume parity (ISSUE 11): the resume-parity fence
  # again, but through the block-scaled int8 bucketed path — its
  # error-feedback residuals ride the SAME kvres/bucketres checkpoint
  # schema as 2bit, so a preempted quantized run must resume with a
  # bitwise-identical trajectory (docs/RESILIENCE.md recovery matrix;
  # opt out with MXTPU_CHAOS_QUANTIZED=0)
  if [ "${MXTPU_CHAOS_QUANTIZED:-1}" != "0" ]; then
  python - <<'EOF'
import tempfile

import numpy as onp
import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon
from mxnet_tpu.gluon import nn
from mxnet_tpu.gluon.utils import split_and_load
from mxnet_tpu.resilience import (CheckpointManager, faultline,
                                  gather_training_state,
                                  restore_training_state)

CTXS = [mx.cpu(i) for i in range(2)]
COMP = {"type": "int8", "block": 64}

def build(seed):
    mx.random.seed(seed)
    net = nn.HybridSequential()
    net.add(nn.Dense(8, in_units=6, activation="relu"))
    net.add(nn.Dense(4, in_units=8))
    net.initialize(ctx=CTXS)
    tr = gluon.Trainer(net.collect_params(), "sgd",
                       {"learning_rate": 0.1, "momentum": 0.9},
                       kvstore="tpu_ici", compression_params=COMP)
    return net, tr

def batch(t):
    rs = onp.random.RandomState(300 + t)
    return mx.np.array(rs.randn(4, 6).astype(onp.float32))

def step(net, tr, t):
    xs = split_and_load(batch(t), CTXS)
    with autograd.record():
        ls = [(net(xb) ** 2).mean() for xb in xs]
    autograd.backward(ls)
    tr.step(4)

def params_np(net):
    return {k: onp.asarray(p.data()._data)
            for k, p in net.collect_params().items()}

# fault-free reference trajectory
net_a, tr_a = build(seed=11)
for t in range(3):
    step(net_a, tr_a, t)
ref = params_np(net_a)

# chaos run: checkpoint after step 2, preempted during step 3's bucket
# dispatch (the quantized collective itself)
net_b, tr_b = build(seed=11)
for t in range(2):
    step(net_b, tr_b, t)
mgr = CheckpointManager(tempfile.mkdtemp(), async_write=False, rank=0)
arrays, meta = gather_training_state(tr_b, step=2)
assert any(k.startswith("bucketres/") for k in arrays), \
    "int8 bucketer residuals must ride the checkpoint"
mgr.save(2, arrays, meta)
faultline.plan([{"site": "collective.dispatch", "kind": "preempt", "at": 1}])
try:
    step(net_b, tr_b, 2)
    raise SystemExit("ci: FAIL — preemption did not fire")
except faultline.InjectedPreemption:
    pass
faultline.clear()

# 'restarted process': wrong init seed proves restore wins; restore
# runs BEFORE the first step, like a real restart (it materializes the
# kvstore/bucketer itself so the residuals have somewhere to land)
net_c, tr_c = build(seed=77)
s, arrays_r, meta_r = mgr.restore_latest()
assert s == 2 and restore_training_state(arrays_r, meta_r, tr_c) == 2
step(net_c, tr_c, 2)
got = params_np(net_c)
for k in ref:
    assert got[k].tobytes() == ref[k].tobytes(), k
mgr.close()
print("ci: quantized int8 preempt/resume parity bitwise")
EOF
  fi
}
run_storm() {
  # fleet chaos load-storm gate (ISSUE 12): mixed-shape, mixed-priority
  # traffic through a 3-replica fleet WHILE a faultline plan kills one
  # replica mid-storm — zero dropped (non-shed) requests, per-class p99
  # inside the declared SLA, and the failover visible in
  # mxtpu_faults_recovered_total + mxtpu_fleet_failover_seconds
  # (docs/SERVING.md "Fleet"; opt out with MXTPU_CHAOS_STORM=0)
  if [ "${MXTPU_CHAOS_STORM:-1}" != "0" ]; then
    MXNET_LOCKSCAN_WITNESS="${MXNET_LOCKSCAN_WITNESS:-1}" \
      python -m tools.storm --gate
  fi
}
run_endure() {
  # elastic endurance gate (ISSUE 13): one emulated 3-host pod driven
  # through two preemptions (same topology -> bitwise trajectory parity
  # vs the fault-free oracle) and one PERMANENT host kill (dead_node
  # fault -> re-shard onto the 2 survivors, linear lr rule, per-host
  # throughput back to >=95% of pre-fault within the recovery window),
  # visible in mxtpu_elastic_reshards_total and
  # mxtpu_faults_recovered_total{kvstore.kv,dead_node}
  # (docs/RESILIENCE.md "Elastic recovery"; opt out with
  # MXTPU_CHAOS_ENDURE=0)
  if [ "${MXTPU_CHAOS_ENDURE:-1}" != "0" ]; then
    MXNET_LOCKSCAN_WITNESS="${MXNET_LOCKSCAN_WITNESS:-1}" \
      python -m tools.endure --gate
  fi
}
run_blackbox() {
  # flight-recorder postmortem gate (ISSUE 17): the endure permanent-kill
  # phase with recording on must leave crash dumps the analyzer
  # root-causes to kvstore.kv/dead_node rank=1, and a 20-step fault-free
  # run must yield verdict NONE with recorder overhead <1% of step time
  # (docs/OBSERVABILITY.md "Black box / postmortem"; opt out with
  # MXTPU_CHAOS_BLACKBOX=0)
  if [ "${MXTPU_CHAOS_BLACKBOX:-1}" != "0" ]; then
    python -m tools.blackbox --gate
  fi
}
run_perf()   { python benchmark/opperf/opperf.py --smoke; }
run_dryrun() {
  # pytest already runs the 4-process launcher test; skip it inside the
  # in-process dryrun to keep ci wall-clock bounded
  export MXTPU_DRYRUN_MULTIPROC=0
  # the sharding-recipe rider (ISSUE 16) rides the 8-device pass: a
  # dp2.tp2.pp2 fused step, the tp2 hloscan contract, and the giant-model
  # placement proof all print recipe_verdict: lines (MXTPU_DRYRUN_RECIPE=0
  # opts out)
  for n in 8 6 3 2; do
    python -c "import __graft_entry__ as g; g.dryrun_multichip($n); print('dryrun($n) ok')"
  done
}

case "$stage" in
  lint)    run_lint ;;
  lockscan) run_lockscan ;;
  import)  run_import ;;
  hloscan) run_hloscan ;;
  census)  run_census ;;
  smoke)   run_smoke ;;
  test)    run_test ;;
  chaos)   run_chaos ;;
  storm)   run_storm ;;
  endure)  run_endure ;;
  blackbox) run_blackbox ;;
  perf)    run_perf ;;
  dryrun)  run_dryrun ;;
  all)     run_lint; run_lockscan; run_import; run_hloscan; run_census
           run_smoke; run_test; run_chaos; run_storm; run_endure
           run_blackbox; run_perf; run_dryrun ;;
  *) echo "unknown stage $stage" >&2; exit 2 ;;
esac
