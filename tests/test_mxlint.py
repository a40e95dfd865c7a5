"""mxlint framework tests (ISSUE 5).

Fixture-based true-positive/clean pairs per rule, waiver and baseline
round-trips, reporter schema, and the self-clean gate: the linter run
on this repo's own sources must exit 0 — every live finding is either
fixed or carries a reasoned waiver.
"""
import io
import json
import os
import shutil
import subprocess
import sys

import pytest

from tools.mxlint import core, driver
from tools.mxlint.rules import all_rules
from tools.mxlint.rules.env_doc import (DECLARED_NOOPS, discovered_env_vars,
                                        documented_env_vars)

REPO = core.REPO_ROOT
FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "mxlint_fixtures")


def _lint(name, rule=None):
    findings, _n = driver.lint([os.path.join(FIXTURES, name)])
    if rule is not None:
        findings = [f for f in findings if f.rule == rule]
    return findings


def _unwaived(findings):
    return [f for f in findings if not f.waived]


# -- per-rule TP/clean pairs -----------------------------------------------
@pytest.mark.parametrize("rule,tp,clean,n_expected", [
    ("env-read-at-trace-time", "envread_tp.py", "envread_clean.py", 3),
    ("env-var-undocumented", "envdoc_tp.py", "envdoc_clean.py", 1),
    ("lock-discipline", "locks_tp.py", "locks_clean.py", 3),
    ("host-sync-in-jit", "hostsync_tp.py", "hostsync_clean.py", 3),
    ("bits-as-float", "bits_tp.py", "bits_clean.py", 2),
    ("daemon-thread-no-shutdown", "thread_tp.py", "thread_clean.py", 1),
    ("nondeterministic-trace", "nondet_tp.py", "nondet_clean.py", 4),
    ("swallowed-exception", "swallow_tp.py", "swallow_clean.py", 4),
])
def test_rule_fixture_pair(rule, tp, clean, n_expected):
    hits = _unwaived(_lint(tp, rule))
    assert len(hits) == n_expected, \
        f"{rule} on {tp}: {[(f.line, f.message) for f in hits]}"
    assert all(f.id and f.qualname for f in hits)
    misses = _lint(clean, rule)
    assert not misses, \
        f"{rule} false positives on {clean}: " \
        f"{[(f.line, f.message) for f in misses]}"


def test_rule_names_unique_and_documented():
    rules = all_rules()
    names = [r.name for r in rules]
    assert len(set(names)) == len(names)
    assert all(r.description for r in rules)
    assert len(rules) == 8


# -- waivers ---------------------------------------------------------------
def test_waiver_with_reason_suppresses():
    findings = _lint("waiver_ok.py")
    envreads = [f for f in findings if f.rule == "env-read-at-trace-time"]
    assert len(envreads) == 2   # line-above and trailing-comment forms
    assert all(f.waived for f in envreads)
    assert all(f.waive_reason and "fixture" in f.waive_reason
               for f in envreads)
    assert not [f for f in findings if f.rule == "bad-waiver"]


def test_waiver_without_reason_is_a_finding_and_waives_nothing():
    findings = _lint("waiver_bad.py")
    envreads = [f for f in findings if f.rule == "env-read-at-trace-time"]
    assert len(envreads) == 1 and not envreads[0].waived
    bad = [f for f in findings if f.rule == "bad-waiver"]
    assert len(bad) == 1


# -- stable finding IDs ----------------------------------------------------
def test_finding_ids_stable_across_unrelated_edits(tmp_path):
    src = os.path.join(FIXTURES, "locks_tp.py")
    work = tmp_path / "locks_tp.py"
    shutil.copy(src, work)
    ids_before = sorted(f.id for f in driver.lint([str(work)])[0])
    # push every finding down two lines: IDs must not move
    work.write_text("# unrelated banner\n# more banner\n" +
                    open(src).read())
    ids_after = sorted(f.id for f in driver.lint([str(work)])[0])
    assert ids_before == ids_after


def test_finding_ids_change_when_the_line_changes(tmp_path):
    src = open(os.path.join(FIXTURES, "envread_tp.py")).read()
    work = tmp_path / "envread_tp.py"
    work.write_text(src)
    before = {f.id for f in driver.lint([str(work)])[0]}
    work.write_text(src.replace('"SOME_KNOB", "0"', '"SOME_KNOB", "1"'))
    after = {f.id for f in driver.lint([str(work)])[0]}
    assert before != after


# -- baseline round-trip ---------------------------------------------------
def test_baseline_roundtrip(tmp_path):
    fixture = os.path.join(FIXTURES, "envread_tp.py")
    baseline = str(tmp_path / "baseline.json")
    out = io.StringIO()
    # unbaselined findings fail the run
    assert driver.run([fixture], baseline_path=baseline, out=out) == 1
    # grandfather them
    assert driver.run([fixture], baseline_path=baseline,
                      update_baseline=True, out=out) == 0
    data = json.load(open(baseline))
    assert data["version"] == driver.JSON_SCHEMA_VERSION
    assert len(data["findings"]) == 3
    for entry in data["findings"].values():
        assert {"rule", "path", "qualname", "message"} <= set(entry)
    # now the same findings pass as baselined
    out = io.StringIO()
    assert driver.run([fixture], baseline_path=baseline, out=out) == 0
    assert "baselined" in out.getvalue()


def test_stale_baseline_entries_fail(tmp_path):
    """A baseline naming findings that no longer exist FAILS the run
    (ISSUE 7): the debt was paid, so the entry must be pruned in the
    same change — `--update-baseline` does it and the run goes green."""
    fixture = os.path.join(FIXTURES, "envread_clean.py")
    baseline = str(tmp_path / "baseline.json")
    json.dump({"version": 1, "findings": {
        "deadbeef0000": {"rule": "env-read-at-trace-time",
                         "path": "gone.py", "qualname": "f",
                         "message": "fixed long ago"}}},
              open(baseline, "w"))
    out = io.StringIO()
    assert driver.run([fixture], baseline_path=baseline, out=out) == 1
    assert "FAIL" in out.getvalue()
    assert "deadbeef0000" in out.getvalue()
    # pruning via --update-baseline clears the failure
    assert driver.run([fixture], baseline_path=baseline,
                      update_baseline=True, out=io.StringIO()) == 0
    assert json.load(open(baseline))["findings"] == {}
    assert driver.run([fixture], baseline_path=baseline,
                      out=io.StringIO()) == 0


def test_stale_baseline_ids_in_json_reporter(tmp_path):
    fixture = os.path.join(FIXTURES, "envread_clean.py")
    baseline = str(tmp_path / "baseline.json")
    json.dump({"version": 1, "findings": {
        "deadbeef0000": {"rule": "env-read-at-trace-time",
                         "path": "gone.py", "qualname": "f",
                         "message": "fixed long ago"}}},
              open(baseline, "w"))
    out = io.StringIO()
    assert driver.run([fixture], baseline_path=baseline, fmt="json",
                      out=out) == 1
    payload = json.loads(out.getvalue())
    assert payload["stale_baseline_ids"] == ["deadbeef0000"]
    assert payload["summary"]["unbaselined"] == 0


# -- JSON reporter schema --------------------------------------------------
def test_json_reporter_schema():
    out = io.StringIO()
    rc = driver.run([os.path.join(FIXTURES, "locks_tp.py")],
                    baseline_path=None, fmt="json", out=out)
    assert rc == 1
    payload = json.loads(out.getvalue())
    assert payload["version"] == driver.JSON_SCHEMA_VERSION
    assert payload["tool"] == "mxlint"
    assert payload["files_scanned"] == 1
    assert payload["summary"]["total"] == payload["summary"]["unbaselined"] \
        == len(payload["findings"]) == 3
    for f in payload["findings"]:
        assert {"id", "rule", "path", "line", "col", "qualname", "message",
                "waived", "waive_reason", "baselined"} <= set(f)
        assert f["rule"] == "lock-discipline"
        assert f["qualname"].startswith("Counter.")


# -- parse errors surface as findings --------------------------------------
def test_syntax_error_is_a_finding(tmp_path):
    bad = tmp_path / "broken.py"
    bad.write_text("def f(:\n    pass\n")
    findings, _ = driver.lint([str(bad)])
    assert [f.rule for f in findings] == ["parse-error"]


# -- the gate itself -------------------------------------------------------
def test_mxlint_self_clean():
    """`python -m tools.mxlint` on the repo exits 0: every live finding
    is fixed or carries a reasoned waiver, and the baseline stays
    near-empty (the CI gate in tools/ci.sh)."""
    r = subprocess.run([sys.executable, "-m", "tools.mxlint"],
                       capture_output=True, text=True, cwd=REPO, timeout=180)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "clean" in r.stdout


def test_cli_reports_fixture_findings_nonzero():
    r = subprocess.run(
        [sys.executable, "-m", "tools.mxlint", "tests/mxlint_fixtures",
         "--no-baseline"],
        capture_output=True, text=True, cwd=REPO, timeout=180)
    assert r.returncode == 1
    assert "[lock-discipline]" in r.stdout
    assert "[bad-waiver]" in r.stdout


def test_cli_list_rules():
    r = subprocess.run([sys.executable, "-m", "tools.mxlint",
                        "--list-rules"],
                       capture_output=True, text=True, cwd=REPO, timeout=60)
    assert r.returncode == 0
    for name in ("env-read-at-trace-time", "env-var-undocumented",
                 "lock-discipline", "host-sync-in-jit", "bits-as-float",
                 "daemon-thread-no-shutdown", "nondeterministic-trace",
                 "swallowed-exception"):
        assert name in r.stdout


# -- env inventory (the other half lives in test_env_vars.py) --------------
def test_discovered_env_vars_sees_known_sites():
    inv = discovered_env_vars()
    assert "MXNET_SEED" in inv
    assert any(p == "mxnet_tpu/env.py" for p, _l in inv["MXNET_SEED"])
    assert "MXNET_TELEMETRY_STEADY_STEPS" in inv  # telemetry/watchdog.py
    assert "MXNET_ENGINE_DEBUG" in inv    # hoisted read in ops/invoke.py


def test_documented_env_vars_matches_live_describe():
    import mxnet_tpu as mx
    assert documented_env_vars() == {n for n, _v, _h in mx.env.describe()}
    assert DECLARED_NOOPS < documented_env_vars()
