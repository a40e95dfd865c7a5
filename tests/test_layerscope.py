"""Layer-census tests (ISSUE 8).

The chain under test, end to end: Gluon blocks push
``jax.named_scope(block.name)`` around ``forward`` so compiled HLO op
metadata carries the layer hierarchy; ``mxnet_tpu.analysis.census``
buckets a per-instruction cost model by that hierarchy, classifies each
bucket against the chip roofline, and fences the result with MFU-floor
contracts; ``tools/layerscope`` is the driver/baseline/report layer.
Heavy captures (the dp FusedTrainStep and the ResNet profile on the
virtual 8-device mesh) compile once per module.
"""
import io
import json
import logging

import pytest

from mxnet_tpu.analysis import census
from mxnet_tpu.telemetry.registry import MetricsRegistry
from tools.layerscope import driver


@pytest.fixture(scope="module")
def dp_doc():
    return census.census_one("fused_train_step_dp")


@pytest.fixture(scope="module")
def resnet_doc():
    return census.census_one("resnet_profile")


# -- name-scope propagation ------------------------------------------------
def test_named_scopes_reach_compiled_hlo():
    """Block names must survive trace -> lower -> XLA optimization as
    ``op_name`` metadata, fwd AND bwd, on the virtual mesh."""
    import numpy as onp

    import mxnet_tpu as mx
    from mxnet_tpu.gluon import FusedTrainStep, Trainer, loss as gloss, nn
    from mxnet_tpu.gluon.block import HybridBlock

    class Net(HybridBlock):
        def __init__(self):
            super().__init__()
            self.proj = nn.Dense(16, in_units=8)
            self.out = nn.Dense(4, in_units=16)
            self.loss_fn = gloss.SoftmaxCrossEntropyLoss()

        def forward(self, x, y):
            return self.loss_fn(self.out(self.proj(x)), y)

    net = Net()
    net.initialize()
    step = FusedTrainStep(net, Trainer(net.collect_params(), "sgd",
                                       {"learning_rate": 0.1}))
    x = mx.np.array(onp.ones((4, 8), onp.float32))
    y = mx.np.array(onp.zeros((4,), onp.int32))
    hlo = step.lower(x, y, batch_size=4).compile().as_text()

    for layer in ("proj", "out", "loss_fn"):
        assert f"/{layer}/" in hlo, f"scope {layer!r} missing from HLO"
    assert "transpose(" in hlo      # the backward pass is scoped too
    assert "optimizer/" in hlo      # fused update is a census row


def test_block_name_follows_registration():
    from mxnet_tpu.gluon import nn

    seq = nn.HybridSequential()
    seq.add(nn.Dense(4, in_units=4))
    assert seq.name == "HybridSequential"   # root: class name
    child = next(iter(seq._children.values()))
    assert child.name == child._scope_name  # child: registration attr


# -- op_name parsing -------------------------------------------------------
@pytest.mark.parametrize("op_name,expected", [
    ("jit(fused)/jit(main)/jvp(Net)/proj/dot_general",
     (("Net", "proj"), "fwd")),
    ("jit(fused)/jit(main)/transpose(jvp(Net))/proj/dot_general",
     (("Net", "proj"), "bwd")),
    ("jit(f)/jit(main)/jvp(Net)/loss_fn/jit(log_softmax)/reduce_max",
     (("Net", "loss_fn"), "fwd")),      # sub-jit frames are not layers
    ("jit(f)/jit(main)/optimizer/mul", (("optimizer",), "fwd")),
    ("", ((), "fwd")),
])
def test_parse_op_name(op_name, expected):
    assert census.parse_op_name(op_name) == expected


# -- cost_analysis harvesting (the single shared implementation) -----------
def test_harvest_cost_analysis_normalizes():
    raw = {"flops": 10.0, "bytes accessed": 4.0, "utilization": 0.5}
    want = {"flops": 10.0, "bytes_accessed": 4.0, "transcendentals": 0.0}
    assert census.harvest_cost_analysis(raw) == want
    assert census.harvest_cost_analysis([raw]) == want   # list-wrapped
    assert census.harvest_cost_analysis(None) == {
        "flops": 0.0, "bytes_accessed": 0.0, "transcendentals": 0.0}
    assert census.harvest_cost_analysis([]) == {
        "flops": 0.0, "bytes_accessed": 0.0, "transcendentals": 0.0}


# -- per-instruction cost model --------------------------------------------
_TINY_HLO = """\
HloModule tiny

ENTRY %main (p0: f32[8,16], p1: f32[16,4]) -> f32[8,4] {
  %p0 = f32[8,16] parameter(0)
  %p1 = f32[16,4] parameter(1)
  %dot.1 = f32[8,4] dot(f32[8,16] %p0, f32[16,4] %p1), lhs_contracting_dims={1}, rhs_contracting_dims={0}, metadata={op_name="jit(f)/jit(main)/jvp(Net)/proj/dot_general"}
  ROOT %exp.1 = f32[8,4] exponential(f32[8,4] %dot.1)
}
"""


def test_cost_model_dot_and_inheritance():
    recs = {r["name"]: r for r in census.per_instruction_costs(_TINY_HLO)}
    dot = recs["dot.1"]
    assert dot["flops"] == 2.0 * 8 * 4 * 16
    assert dot["bytes"] == (8 * 16 + 16 * 4 + 8 * 4) * 4
    # the metadata-less exponential inherits its operand's op_name, so a
    # compiler cosmetic can never grow the unattributed bucket
    exp = recs["exp.1"]
    assert exp["op_name"] == dot["op_name"]
    assert exp["transcendentals"] == 8 * 4


def test_bucket_costs_attribution():
    recs = census.per_instruction_costs(_TINY_HLO)
    rows = {r["layer"]: r for r in census.bucket_costs(recs, ["proj"])}
    assert rows["Net/proj"]["attributed"]
    assert rows["Net/proj"]["flops"] > 0
    rows = census.bucket_costs(recs, ["nothing"])
    assert all(r["layer"] == census.UNATTRIBUTED for r in rows)


def test_classify_bound():
    peaks = {"flops": 100.0, "bw": 10.0, "launch_s": 1.0}
    assert census.classify_bound(1000.0, 1.0, 1, peaks)[0] == "MXU-bound"
    assert census.classify_bound(1.0, 1000.0, 1, peaks)[0] == "HBM-bound"
    assert census.classify_bound(1.0, 1.0, 5, peaks) == ("launch-bound", 5.0)


# -- the real entry points (acceptance criteria) ---------------------------
def test_dp_census_attribution_over_90pct(dp_doc):
    assert dp_doc["attributed_flops_fraction"] >= 0.90
    layers = {r["layer"] for r in dp_doc["rows"] if r["attributed"]}
    assert "optimizer" in layers
    assert any("_NetWithLoss" in l for l in layers)
    # no giant anonymous bucket
    unattr = sum(r["flops"] for r in dp_doc["rows"] if not r["attributed"])
    assert unattr < 0.10 * dp_doc["totals"]["flops"]
    assert not [f for f in dp_doc["findings"] if not f["waived"]]


def test_dp_census_cross_checks_xla_aggregate(dp_doc):
    xla = dp_doc["totals"]["xla_flops"]
    assert xla and 0.5 < dp_doc["totals"]["flops"] / xla < 2.0


def test_resnet_waivers_retired_floors_pass(resnet_doc):
    # PR 18: the stem and BN-backward floors pass outright (s2d stem +
    # fused conv+BN units), so the contract carries no waivers and the
    # census emits no findings at all
    assert resnet_doc["findings"] == []
    assert not resnet_doc["contract"].get("waivers")
    floors = resnet_doc["contract"]["mfu_floors"]
    assert floors == {"stem": 0.50, "bn@bwd": 0.10}
    by_key = {f"{r['layer']}@{r['phase']}": r for r in resnet_doc["rows"]}
    assert by_key["_ResNetProfile/stem@fwd"]["mfu_sol"] >= 0.50
    assert by_key["_ResNetProfile/stem@bwd"]["mfu_sol"] >= 0.50
    bn_bwd = [r for r in resnet_doc["rows"]
              if "bn" in r["layer"] and r["phase"] == "bwd"]
    assert bn_bwd and all(r["mfu_sol"] >= 0.10 for r in bn_bwd)


def test_json_artifact_round_trips(dp_doc):
    again = json.loads(census.dumps(dp_doc))
    assert again == dp_doc
    assert again["schema"] == census.SCHEMA
    assert set(again["rows"][0]) >= {
        "layer", "phase", "flops", "bytes", "bound", "pct_time",
        "mfu_sol", "mfu", "tf_per_s", "gb_per_s", "intensity"}


# -- contract + waiver semantics -------------------------------------------
def _synthetic_doc(mfu_sol=0.05):
    row = {"layer": "Net/slow", "phase": "bwd", "attributed": True,
           "flops": 100.0, "bytes": 400.0, "transcendentals": 0.0,
           "instructions": 1, "bound": "HBM-bound", "modeled_time_s": 1.0,
           "intensity": 0.25, "mfu_sol": mfu_sol, "mfu": None,
           "tf_per_s": None, "gb_per_s": None, "measured_time_s": None,
           "pct_time": 100.0}
    return {"attributed_flops_fraction": 1.0, "rows": [row],
            "peaks": dict(census.PEAKS[census.DEFAULT_DEVICE])}


def test_contract_unknown_key_raises():
    with pytest.raises(ValueError, match="unknown census contract"):
        census.evaluate_contract(_synthetic_doc(), {"mfu_floor": {}})


def test_mfu_floor_violation_and_waiver():
    doc = _synthetic_doc(mfu_sol=0.05)
    contract = {"mfu_floors": {"slow@bwd": 0.5}}
    (f,) = census.evaluate_contract(doc, contract)
    assert f["rule"] == "mfu-floor" and not f["waived"]
    assert f["key"] == "Net/slow@bwd"

    contract["waivers"] = [
        {"rule": "mfu-floor", "match": "slow", "reason": "known offender"}]
    (f,) = census.evaluate_contract(doc, contract)
    assert f["waived"] and f["reason"] == "known offender"


def test_reasonless_waiver_waives_nothing():
    doc = _synthetic_doc(mfu_sol=0.05)
    contract = {"mfu_floors": {"slow": 0.5},
                "waivers": [{"rule": "mfu-floor", "match": "slow"}]}
    findings = census.evaluate_contract(doc, contract)
    rules = sorted(f["rule"] for f in findings)
    assert rules == ["bad-waiver", "mfu-floor"]
    assert not any(f["waived"] for f in findings)


def test_stale_waiver_and_stale_floor():
    doc = _synthetic_doc(mfu_sol=0.9)      # healthy: floor satisfied
    contract = {
        "mfu_floors": {"slow": 0.5, "gone_layer": 0.5},
        "waivers": [{"rule": "mfu-floor", "match": "slow",
                     "reason": "no longer needed"}]}
    findings = census.evaluate_contract(doc, contract)
    rules = sorted(f["rule"] for f in findings)
    assert rules == ["stale-floor", "stale-waiver"]


def test_attribution_coverage_finding():
    doc = _synthetic_doc()
    doc["attributed_flops_fraction"] = 0.5
    (f,) = census.evaluate_contract(doc, {"min_attributed_flops": 0.9})
    assert f["rule"] == "attribution-coverage"


# -- telemetry -------------------------------------------------------------
def test_census_gauges_in_exposition(dp_doc):
    reg = MetricsRegistry()
    census.publish_metrics(dp_doc, registry=reg)
    text = reg.export_prometheus()
    assert "mxtpu_layer_mfu" in text
    assert "mxtpu_layer_time_fraction" in text
    v = reg.get_sample_value("mxtpu_layer_mfu", {
        "entry": "fused_train_step_dp", "layer": "optimizer@fwd"})
    assert v is not None and 0.0 <= v <= 1.0


def test_watchdog_warning_names_scope_root(caplog):
    from mxnet_tpu.telemetry.watchdog import RetraceWatchdog

    class FakeJit:
        def __init__(self):
            self.size = 1

        def _cache_size(self):
            return self.size

    wd = RetraceWatchdog(steady_after=1, registry=MetricsRegistry())
    fn = FakeJit()
    with caplog.at_level(logging.WARNING, logger="mxnet_tpu.telemetry"):
        wd.observe(fn, "Net.hybrid_forward", scope_root="Net")
        fn.size = 2
        wd.observe(fn, "Net.hybrid_forward", scope_root="Net")
        fn.size = 3
        wd.observe(fn, "Net.hybrid_forward", scope_root="Net")
    warned = [r.message for r in caplog.records if "retrace" in r.message]
    assert warned and "[name-stack root 'Net']" in warned[-1]


# -- the driver (tools/layerscope) -----------------------------------------
def _driver_doc(**over):
    doc = _synthetic_doc()
    doc.update(schema=census.SCHEMA, entry="synthetic",
               device="tpu-v5e", mode="cost-model",
               totals={"flops": 100.0, "bytes": 400.0, "instructions": 1,
                       "modeled_time_s": 1.0, "xla_flops": None,
                       "xla_bytes_accessed": None,
                       "xla_transcendentals": None},
               contract={}, meta={}, findings=[])
    doc.update(over)
    return doc


def test_driver_clean_run_exits_zero():
    out = io.StringIO()
    rc = driver.run(docs=[_driver_doc()], artifacts=False, metrics=False,
                    out=out)
    assert rc == 0
    assert "layerscope: clean" in out.getvalue()
    assert "layer_census_top_sag" in out.getvalue()


def test_driver_live_finding_exits_one():
    doc = _driver_doc(findings=[{
        "rule": "mfu-floor", "key": "Net/slow@bwd", "message": "sagging",
        "waived": False, "reason": None}])
    out = io.StringIO()
    rc = driver.run(docs=[doc], artifacts=False, metrics=False, out=out)
    assert rc == 1
    assert "mfu-floor" in out.getvalue()


def test_driver_baseline_round_trip_and_staleness(tmp_path):
    base = str(tmp_path / "baseline.json")
    finding = {"rule": "mfu-floor", "key": "Net/slow@bwd",
               "message": "sagging", "waived": False, "reason": None}
    doc = _driver_doc(findings=[finding])
    rc = driver.run(docs=[doc], baseline_path=base, update_baseline=True,
                    artifacts=False, metrics=False, out=io.StringIO())
    assert rc == 0
    # baselined: the same finding no longer fails
    rc = driver.run(docs=[doc], baseline_path=base, artifacts=False,
                    metrics=False, out=io.StringIO())
    assert rc == 0
    # fixed offender -> the baseline entry is stale -> FAIL
    out = io.StringIO()
    rc = driver.run(docs=[_driver_doc()], baseline_path=base,
                    artifacts=False, metrics=False, out=out)
    assert rc == 1
    assert "stale" in out.getvalue()


def test_finding_ids_stable():
    f = {"rule": "mfu-floor", "key": "Net/slow@bwd"}
    assert driver.finding_id("e", f) == driver.finding_id("e", dict(f))
    assert driver.finding_id("e", f) != driver.finding_id("e2", f)


def test_top_sag_and_verdicts(dp_doc):
    sag = driver.top_sag(dp_doc)
    assert 0 < len(sag) <= 5
    assert any("optimizer@fwd" in s for s in sag)
    assert all(any(b in s for b in ("MXU-bound", "HBM-bound",
                                    "launch-bound")) for s in sag)
    lines = driver.verdict_lines([dp_doc])
    assert len(lines) == len(driver.RULES)
    assert all("PASS" in l for l in lines)


def test_checked_in_baseline_is_empty():
    assert driver.load_baseline(driver.DEFAULT_BASELINE) == {}


def test_committed_artifacts_parse(dp_doc):
    path = driver.artifact_path("fused_train_step_dp")
    with open(path, "r", encoding="utf-8") as f:
        doc = json.load(f)
    assert doc["schema"] == census.SCHEMA
    assert doc["attributed_flops_fraction"] >= 0.90
