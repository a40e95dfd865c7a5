"""hloscan framework tests (ISSUE 7).

Mirrors test_mxlint.py one layer down: fixture-based TP/clean pairs per
rule (live-lowered tiny jax programs, see tests/hloscan_fixtures/),
contract-waiver and baseline round-trips, stable finding IDs across
instruction renumbering, reporter schema — and the gate itself: the
scan of the REAL entry points (train step on the virtual 8-device
mesh, bucketed allreduce, flash attention, serve endpoint) must come
back clean against the checked-in EMPTY baseline.
"""
import importlib.util
import io
import json
import os
import re
import subprocess
import sys

import pytest

from tools.hloscan import core, driver, hlo
from tools.hloscan.rules import all_rules

REPO = core.REPO_ROOT
FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "hloscan_fixtures")

_spec = importlib.util.spec_from_file_location(
    "hloscan_fixture_programs", os.path.join(FIXTURES, "programs.py"))
programs = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(programs)


def _hlo_fixture(fname):
    with open(os.path.join(FIXTURES, fname), "r", encoding="utf-8") as f:
        return f.read()


def _live(findings, rule=None):
    return [f for f in findings if not f.waived and not f.baselined
            and (rule is None or f.rule == rule)]


# -- HLO parser (on the hand-written optimized-style fixtures) -------------
def test_parse_optimized_style_module():
    mod = hlo.parse(_hlo_fixture("paired_overlap_clean.hlo"))
    assert mod.is_scheduled and mod.num_partitions == 8
    assert set(mod.computations) == {"add_f32", "main"}
    assert mod.entry.name == "main"
    out = mod.entry.by_name["out"]
    assert out.is_root and out.opcode == "tuple"
    assert out.operands == ("ard", "dot")
    dot = mod.entry.by_name["dot"]
    assert dot.clean_shape == "f32[16,16]"      # layout braces stripped
    assert dot.result_dtypes == ("f32",)
    ars = mod.entry.by_name["ars"]
    assert ars.opcode == "all-reduce-start"
    assert ars.called_computations() == ["add_f32"]


def test_parse_lowered_style_module():
    art, _clean, _n = programs.dtype_cliff_pair()
    mod = art.module("lowered")
    assert mod is not None and mod.entry is not None
    ops = {i.opcode for i in mod.entry.instructions}
    assert "dot" in ops and "convert" in ops
    # operand edges resolve in the bare-name style too
    dots = [i for i in mod.entry.instructions if i.opcode == "dot"]
    assert all(op in mod.entry.by_name
               for d in dots for op in d.operands)


def test_dependence_analysis():
    mod = hlo.parse(_hlo_fixture("paired_overlap_clean.hlo"))
    comp = mod.entry
    ard = comp.by_name["ard"]
    assert comp.by_name["ars"] in comp.ancestors(ard)
    assert comp.by_name["out"] in comp.descendants(ard)
    assert comp.by_name["dot"] not in comp.ancestors(ard)
    assert comp.by_name["dot"] not in comp.descendants(ard)


def test_collective_counts_count_issues_not_instructions():
    mod = hlo.parse(_hlo_fixture("paired_overlap_tp.hlo"))
    # a -start/-done pair is ONE launch
    assert hlo.collective_counts(mod) == {"all-reduce": 1}


# -- paired overlap mode (TPU-shaped modules, hand-written) ----------------
def test_paired_overlap_modes():
    contract = {"expect_overlap": True}
    tp = core.Artifact(name="fixture.paired_tp", kind="fixture",
                       optimized=_hlo_fixture("paired_overlap_tp.hlo"),
                       contract=contract)
    hits = _live(driver.scan([tp]), "collective-overlap")
    assert len(hits) == 1
    assert "between start and done" in hits[0].message
    clean = core.Artifact(name="fixture.paired_clean", kind="fixture",
                          optimized=_hlo_fixture("paired_overlap_clean.hlo"),
                          contract=contract)
    assert not _live(driver.scan([clean]), "collective-overlap")


def test_overlap_report_shapes():
    rep_tp = hlo.overlap_report(
        hlo.parse(_hlo_fixture("paired_overlap_tp.hlo")).entry)
    assert [r["mode"] for r in rep_tp] == ["paired"]
    assert rep_tp[0]["compute"] == []
    rep_clean = hlo.overlap_report(
        hlo.parse(_hlo_fixture("paired_overlap_clean.hlo")).entry)
    assert [i.opcode for i in rep_clean[0]["compute"]] == ["dot"]


# -- per-rule TP/clean pairs (live-lowered programs) -----------------------
@pytest.mark.parametrize("rule", sorted(programs.RULE_PAIRS))
def test_rule_fixture_pair(rule):
    tp, clean, n_expected = programs.pair(rule)
    hits = _live(driver.scan([tp]), rule)
    assert len(hits) == n_expected, \
        f"{rule} on {tp.name}: {[(f.key, f.message) for f in hits]}"
    assert all(f.id and f.key for f in hits)
    misses = driver.scan([clean])
    assert not _live(misses), \
        f"{rule} false positives on {clean.name}: " \
        f"{[(f.rule, f.key, f.message) for f in misses]}"


def test_rule_names_unique_and_documented():
    rules = all_rules()
    names = [r.name for r in rules]
    assert len(set(names)) == len(names)
    assert all(r.description for r in rules)
    assert len(rules) == 5


def test_collective_free_contract_flags_any_collective():
    art = programs.artifact_from_texts(
        "fixture.not_collective_free", programs.serial_allreduce_texts(),
        {"collective_free": True})
    hits = _live(driver.scan([art]), "launch-count")
    assert len(hits) == 1 and hits[0].key == "collective-free"


def test_launch_count_total_form():
    texts = programs.serial_allreduce_texts()
    ok = programs.artifact_from_texts("fixture.total_ok", texts,
                                      {"expected_collectives": 1})
    assert not _live(driver.scan([ok]))
    bad = programs.artifact_from_texts("fixture.total_bad", texts,
                                       {"expected_collectives": 2})
    hits = _live(driver.scan([bad]), "launch-count")
    assert len(hits) == 1 and hits[0].key == "count:total"
    assert "traced away" in hits[0].message


def test_unknown_contract_key_raises():
    with pytest.raises(ValueError, match="expect_overlpa"):
        core.Artifact(name="typo", kind="fixture",
                      contract={"expect_overlpa": True})


# -- waivers (contract-declared; HLO has no inline comments) ---------------
def test_reasoned_waiver_suppresses():
    art = programs.artifact_from_texts(
        "fixture.waived", programs.serial_allreduce_texts(),
        {"expected_collectives": {"all-reduce": 4},
         "waivers": [{"rule": "launch-count", "match": "count:",
                      "reason": "fixture: census pinned by a later PR"}]})
    findings = driver.scan([art])
    assert len(findings) == 1 and findings[0].waived
    assert "fixture" in findings[0].waive_reason
    assert not _live(findings)


def test_waiver_match_must_hit_the_key():
    art = programs.artifact_from_texts(
        "fixture.mismatched_waiver", programs.serial_allreduce_texts(),
        {"expected_collectives": {"all-reduce": 4},
         "waivers": [{"rule": "launch-count", "match": "count:all-gather",
                      "reason": "wrong opcode — must not apply"}]})
    hits = _live(driver.scan([art]), "launch-count")
    assert len(hits) == 1 and not hits[0].waived


def test_waiver_without_reason_is_a_finding_and_waives_nothing():
    art = programs.artifact_from_texts(
        "fixture.bad_waiver", programs.serial_allreduce_texts(),
        {"expected_collectives": {"all-reduce": 4},
         "waivers": [{"rule": "launch-count"}]})
    findings = driver.scan([art])
    assert len(_live(findings, "launch-count")) == 1
    bad = _live(findings, "bad-waiver")
    assert len(bad) == 1 and bad[0].key == "waiver[0]:launch-count"


# -- stable finding IDs ----------------------------------------------------
def _renumber(text, offset=100):
    """Simulate a recompile: push every instruction numeric suffix by
    ``offset`` (XLA renumbers `convert.9` -> `convert.17` on any
    unrelated edit; finding IDs must not move)."""
    return re.sub(r"\.(\d+)\b", lambda m: f".{int(m.group(1)) + offset}",
                  text)


def test_finding_ids_stable_across_instruction_renumbering():
    tp, _clean, _n = programs.dtype_cliff_pair()
    before = sorted(f.id for f in _live(driver.scan([tp])))
    renumbered = core.Artifact(
        name=tp.name, kind=tp.kind, jaxpr=tp.jaxpr,
        lowered=_renumber(tp.lowered),
        optimized=_renumber(tp.optimized) if tp.optimized else None,
        contract=tp.contract)
    after = sorted(f.id for f in _live(driver.scan([renumbered])))
    assert before == after and len(before) == 3


def test_finding_ids_differ_across_artifacts_and_rules():
    texts = programs.serial_allreduce_texts()
    a = programs.artifact_from_texts("fixture.census_a", texts,
                                     {"expected_collectives": {"all-reduce": 4}})
    b = programs.artifact_from_texts("fixture.census_b", texts,
                                     {"expected_collectives": {"all-reduce": 4}})
    ids = {f.id for f in driver.scan([a, b])}
    assert len(ids) == 2   # same rule+key, different artifact -> different id


# -- baseline round-trip ---------------------------------------------------
def test_baseline_roundtrip(tmp_path):
    tp, _clean, n = programs.dtype_cliff_pair()
    baseline = str(tmp_path / "baseline.json")
    out = io.StringIO()
    assert driver.run(artifacts=[tp], baseline_path=baseline,
                      metrics=False, out=out) == 1
    # grandfather the findings
    assert driver.run(artifacts=[tp], baseline_path=baseline,
                      update_baseline=True, metrics=False,
                      out=io.StringIO()) == 0
    data = json.load(open(baseline))
    assert data["version"] == driver.JSON_SCHEMA_VERSION
    assert len(data["findings"]) == n
    for entry in data["findings"].values():
        assert {"rule", "artifact", "key", "message"} <= set(entry)
    out = io.StringIO()
    assert driver.run(artifacts=[tp], baseline_path=baseline,
                      metrics=False, out=out) == 0
    assert "baselined" in out.getvalue()


def test_stale_baseline_entries_fail(tmp_path):
    """A baseline naming findings that no longer exist FAILS the scan —
    the debt was paid, prune the entry in the same change."""
    _tp, clean, _n = programs.dtype_cliff_pair()
    baseline = str(tmp_path / "baseline.json")
    json.dump({"version": 1, "findings": {
        "deadbeef0000": {"rule": "dtype-cliff", "artifact": "gone",
                         "key": "convert#0", "message": "fixed long ago"}}},
              open(baseline, "w"))
    out = io.StringIO()
    assert driver.run(artifacts=[clean], baseline_path=baseline,
                      metrics=False, out=out) == 1
    assert "FAIL" in out.getvalue() and "deadbeef0000" in out.getvalue()
    assert driver.run(artifacts=[clean], baseline_path=baseline,
                      update_baseline=True, metrics=False,
                      out=io.StringIO()) == 0
    assert json.load(open(baseline))["findings"] == {}
    assert driver.run(artifacts=[clean], baseline_path=baseline,
                      metrics=False, out=io.StringIO()) == 0


# -- reporters -------------------------------------------------------------
def test_json_reporter_schema():
    tp, _clean, n = programs.dtype_cliff_pair()
    out = io.StringIO()
    rc = driver.run(artifacts=[tp], baseline_path=None, fmt="json",
                    metrics=False, out=out)
    assert rc == 1
    payload = json.loads(out.getvalue())
    assert payload["version"] == driver.JSON_SCHEMA_VERSION
    assert payload["tool"] == "hloscan"
    assert payload["artifacts"] == [tp.name]
    assert payload["summary"]["total"] == payload["summary"]["unbaselined"] \
        == len(payload["findings"]) == n
    assert payload["stale_baseline_ids"] == []
    for f in payload["findings"]:
        assert {"id", "rule", "artifact", "key", "where", "message",
                "waived", "waive_reason", "baselined"} <= set(f)
        assert f["rule"] == "dtype-cliff"


def test_verdict_lines():
    tp, _clean, _n = programs.launch_count_pair()
    artifacts = [tp]
    lines = driver.verdict_lines(driver.scan(artifacts), artifacts)
    assert len(lines) == len(all_rules())
    by_rule = {ln.split()[1]: ln for ln in lines}
    assert "FAIL (1)" in by_rule["launch-count"]
    assert "PASS" in by_rule["collective-overlap"]
    assert all("[1 artifacts]" in ln for ln in lines)


def test_metrics_census_published():
    from mxnet_tpu import telemetry
    tp, _clean, n = programs.dtype_cliff_pair()
    assert driver.publish_metrics(driver.scan([tp]))
    reg = telemetry.default_registry()
    assert reg.get_sample_value(
        "mxtpu_hloscan_findings",
        {"rule": "dtype-cliff", "disposition": "live"}) == n
    assert reg.get_sample_value(
        "mxtpu_hloscan_findings",
        {"rule": "launch-count", "disposition": "live"}) == 0


def test_cli_list_rules():
    r = subprocess.run([sys.executable, "-m", "tools.hloscan",
                        "--list-rules"],
                       capture_output=True, text=True, cwd=REPO, timeout=60)
    assert r.returncode == 0
    for name in ("collective-overlap", "no-host-roundtrip", "dtype-cliff",
                 "resharding-detector", "launch-count"):
        assert name in r.stdout


# -- the gate itself: real entry points vs the EMPTY baseline --------------
@pytest.fixture(scope="module")
def real_artifacts():
    """Capture every registered entry point once (in-process, ~3s)."""
    return driver.default_artifacts()


def test_real_entrypoints_scan_clean(real_artifacts):
    """The CI gate (tools/ci.sh): the train step, bucketed allreduce,
    flash attention, and serve endpoint all honor their compiled-program
    contracts with the checked-in baseline EMPTY."""
    assert json.load(open(driver.DEFAULT_BASELINE))["findings"] == {}, \
        "tools/hloscan_baseline.json must stay empty — fix the program " \
        "or add a reasoned contract waiver instead of grandfathering"
    out = io.StringIO()
    rc = driver.run(artifacts=real_artifacts,
                    baseline_path=driver.DEFAULT_BASELINE,
                    metrics=False, out=out, verdicts=True)
    assert rc == 0, out.getvalue()
    assert "hloscan: clean" in out.getvalue()
    for line in driver.verdict_lines(driver.scan(real_artifacts),
                                     real_artifacts):
        assert "PASS" in line, line


def test_real_artifact_inventory(real_artifacts):
    names = {a.name for a in real_artifacts}
    assert names == {"fused_train_step.dp",
                     "fused_train_step.recipe_tp2",
                     "allreduce.bucket_dense",
                     "allreduce.bucket_2bit", "allreduce.bucket_int8",
                     "allreduce.bucket_fp8",
                     "allreduce.bucket_dense_integrity",
                     "allreduce.bucket_int8_integrity",
                     "allreduce.bucketed_step",
                     "allreduce.bucketed_step_int8",
                     "flash_attention.fwd", "flash_attention.bwd",
                     "serve.endpoint"}
    for a in real_artifacts:
        assert a.best_module is not None, f"{a.name}: no HLO captured"


def test_integrity_artifacts_pin_one_extra_collective(real_artifacts):
    """The ISSUE 14 integrity sideband is a declared contract variant:
    the digest-agreement pmax rides INSIDE the same program — exactly
    one collective beyond the non-integrity twin, zero extra launches
    (defaults unchanged: the plain artifacts keep their counts)."""
    by_name = {a.name: a for a in real_artifacts}
    dense = by_name["allreduce.bucket_dense_integrity"]
    assert dense.contract["expected_collectives"] == {"all-reduce": 2}
    assert hlo.collective_counts(dense.best_module) == {"all-reduce": 2}
    assert dense.meta["mode"] == "integrity"
    int8 = by_name["allreduce.bucket_int8_integrity"]
    assert int8.contract["expected_collectives"] == {"all-reduce": 3}
    assert hlo.collective_counts(int8.best_module) == {"all-reduce": 3}
    assert by_name["allreduce.bucket_dense"].contract[
        "expected_collectives"] == {"all-reduce": 1}


def test_dp_step_census_locks_bucket_collapse(real_artifacts):
    """PR 4's headline, pinned by contract: the resnet50-profile bucketed
    step collapses 160 tensors into 4 buckets at 1 MiB and spells one
    psum per bucket — counted in the lowered module, the program's own
    plan, before XLA's combiner (which under jax 0.9.0 merges all four).
    The dp train step's all-reduces are the partitioner's and only exist
    after XLA's passes: one launch for its four gradients."""
    by_name = {a.name: a for a in real_artifacts}
    dp = by_name["fused_train_step.dp"]
    assert hlo.collective_counts(dp.module("lowered")) == {}
    assert hlo.collective_counts(dp.best_module) == \
        dp.contract["expected_collectives"] == {"all-reduce": 1}
    bucketed = by_name["allreduce.bucketed_step"]
    assert bucketed.meta["n_tensors"] == 160
    assert bucketed.meta["n_buckets"] == 4
    assert bucketed.contract["collectives_stage"] == "lowered"
    assert hlo.collective_counts(bucketed.module("lowered")) == \
        {"all-reduce": 4}


def test_quantized_step_census_keeps_bucket_collapse(real_artifacts):
    """The block-scaled int8 step rides the SAME 4-bucket plan: two
    all-reduce ops per bucket in the HLO (the ~1/256 scale-agreement
    pmax + the widened int8-payload psum), both inside one launch — so
    the runtime launch count the dryrun rider measures stays 4."""
    by_name = {a.name: a for a in real_artifacts}
    q = by_name["allreduce.bucketed_step_int8"]
    assert q.meta["n_tensors"] == 160
    assert q.meta["n_buckets"] == 4
    assert q.contract["expected_collectives"] == {"all-reduce": 8}
    assert q.contract["collectives_stage"] == "lowered"
    assert hlo.collective_counts(q.module("lowered")) == {"all-reduce": 8}
    for name in ("allreduce.bucket_int8", "allreduce.bucket_fp8"):
        a = by_name[name]
        assert a.contract["expected_collectives"] == {"all-reduce": 2}
        assert hlo.collective_counts(a.best_module) == {"all-reduce": 2}


def test_dp_step_overlap_is_real(real_artifacts):
    """Every gradient all-reduce in the dp step has compute independent
    of it — the overlap PASS is not vacuous."""
    dp = next(a for a in real_artifacts if a.name == "fused_train_step.dp")
    reports = hlo.overlap_report(dp.best_module.entry)
    issues = [r for r in reports
              if hlo.base_collective(r["instr"].opcode) == "all-reduce"]
    assert issues
    for rep in issues:
        assert len(rep["compute"]) > 0, \
            f"{rep['instr'].name}: no hideable compute"
