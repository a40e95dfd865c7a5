"""Flight recorder + blackbox analyzer tests (ISSUE 17): bounded ring,
atomic dumps, emitter taps, clock-skew-corrected timeline merge, and
root-cause verdicts.  The live chaos scenarios (endure preempt /
dead-node / straggler / bitflip / divergence, storm replica kill) assert
their own blackbox root-cause checks inside tools/endure.py and
tools/storm.py — here the scenario verdicts run on synthetic multi-host
dumps so the analyzer's ordering and attribution logic is pinned without
multi-minute supervisor runs."""
import json
import os

import pytest

from mxnet_tpu import observe
from mxnet_tpu.observe import FlightRecorder
from mxnet_tpu.resilience import faultline
from tools import blackbox

S = 1_000_000_000   # ns per second
TIMEOUT = 60.0      # heartbeat timeout the skew warnings are judged by


def _dump(host, events, generation=0, step=0, dropped=0):
    """A synthetic per-host dump: events are (wall_ns, cat, name,
    payload) on the host's own (possibly skewed) clock."""
    evs = [[1000 + i, int(t), host, generation, cat, name, payload]
           for i, (t, cat, name, payload) in enumerate(events)]
    return {"schema": observe.SCHEMA_VERSION, "host": host,
            "generation": generation, "step": step, "reason": "test",
            "capacity": 4096, "recorded": len(evs) + dropped,
            "dropped": dropped, "dumped_mono_ns": 0, "dumped_wall_ns": 0,
            "events": evs}


def _stamp(true_ns, skew_ns):
    """The subject's wall clock (seconds) at true time ``true_ns``."""
    return (true_ns + skew_ns) / 1e9


def _skewed_pod(skew1_ns, skew2_ns):
    """Three hosts; 1 and 2 skewed.  True causal order: host0 observes
    both peers, host1 records the injected kill of rank 2, host2 goes
    stale, host0 hits the terminal error."""
    h0 = _dump(0, [
        (1 * S, "heartbeat", "observe",
         {"rank": 1, "stamp": _stamp(1 * S, skew1_ns), "stale": False}),
        (2 * S, "heartbeat", "observe",
         {"rank": 2, "stamp": _stamp(2 * S, skew2_ns), "stale": False}),
        (6 * S, "terminal", "DeadNodeError", {"dead_ranks": [2]}),
    ])
    h1 = _dump(1, [
        (3 * S + skew1_ns, "fault", "kvstore.kv/dead_node",
         {"site": "kvstore.kv", "kind": "dead_node", "rank": 2}),
    ])
    h2 = _dump(2, [
        (4 * S + skew2_ns, "heartbeat", "observe",
         {"rank": 1, "stamp": None, "stale": True, "consecutive": 2}),
    ])
    return [h0, h1, h2]


_TRUE_ORDER = ["observe", "observe", "kvstore.kv/dead_node", "observe",
               "DeadNodeError"]


# ---------------------------------------------------------------------------
# recorder: bounded ring + dumps
# ---------------------------------------------------------------------------

def test_ring_bounded_oldest_first():
    rec = FlightRecorder(capacity=16, enabled=True)
    for i in range(40):
        rec.record("c", "e", i=i)
    evs = rec.events()
    assert len(evs) == 16
    assert [e[6]["i"] for e in evs] == list(range(24, 40))
    snap = rec.snapshot()
    assert snap["recorded"] == 40 and snap["dropped"] == 24
    # mono timestamps are non-decreasing within a host
    monos = [e[0] for e in evs]
    assert monos == sorted(monos)


def test_disabled_recorder_is_a_noop(tmp_path):
    rec = FlightRecorder(capacity=8, enabled=False)
    rec.record("c", "e")
    assert rec.events() == []
    assert rec.dump(root=str(tmp_path)) is None
    assert list(tmp_path.iterdir()) == []


def test_env_knobs(monkeypatch):
    monkeypatch.setenv("MXNET_BLACKBOX", "0")
    assert not FlightRecorder().enabled
    monkeypatch.setenv("MXNET_BLACKBOX", "1")
    monkeypatch.setenv("MXNET_BLACKBOX_EVENTS", "32")
    rec = FlightRecorder()
    assert rec.enabled and rec.snapshot()["capacity"] == 32


def test_dump_atomic_keyed_and_schema(tmp_path):
    rec = FlightRecorder(capacity=8, enabled=True)
    rec.set_rank(2)
    rec.set_generation(1)
    rec.set_step(7)
    rec.record("phase", "fwd", seconds=0.25)
    path = rec.dump(reason="unit", root=str(tmp_path))
    assert os.path.basename(path) == \
        "blackbox-host00002-gen001-step0000000007.json"
    assert os.path.dirname(path) == str(tmp_path / "blackbox")
    # atomic: no tmp file survives the rename
    assert not [p for p in os.listdir(os.path.dirname(path))
                if ".tmp" in p]
    with open(path) as f:
        doc = json.load(f)
    assert doc["schema"] == observe.SCHEMA_VERSION
    assert doc["host"] == 2 and doc["generation"] == 1 \
        and doc["step"] == 7 and doc["reason"] == "unit"
    assert doc["events"][0][4:6] == ["phase", "fwd"]
    assert doc["events"][0][6] == {"seconds": 0.25}


def test_dump_dir_env_override(tmp_path, monkeypatch):
    monkeypatch.setenv("MXNET_BLACKBOX_DIR", str(tmp_path / "override"))
    rec = FlightRecorder(capacity=8, enabled=True)
    rec.record("c", "e")
    path = rec.dump(root=str(tmp_path / "ignored"))
    assert os.path.dirname(path) == str(tmp_path / "override")


def test_faultline_tap_feeds_the_recorder():
    observe.reset()
    faultline.clear()
    try:
        faultline.plan([{"site": "data.iterator", "kind": "slow",
                         "delay": 0.0, "at": 1}])
        faultline.check("data.iterator")
    finally:
        faultline.clear()
    faults = [e for e in observe.events() if e[4] == "fault"]
    assert faults and faults[0][5] == "data.iterator/slow"
    verdict = blackbox.analyze([observe.snapshot(reason="unit")])
    assert (verdict["site"], verdict["kind"]) == ("data.iterator", "slow")
    observe.reset()


# ---------------------------------------------------------------------------
# skew correction (satellite: below AND above timeout/2, uncorrectable)
# ---------------------------------------------------------------------------

def test_skew_below_timeout_half_merges_in_causal_order():
    dumps = _skewed_pod(skew1_ns=5 * S, skew2_ns=-9 * S)
    entries, offsets, warnings, _ = blackbox.merge(dumps, timeout=TIMEOUT)
    assert [e["name"] for e in entries] == _TRUE_ORDER
    assert offsets[0] == 0
    assert offsets[1] == pytest.approx(5 * S, abs=S // 100)
    assert offsets[2] == pytest.approx(-9 * S, abs=S // 100)
    assert warnings == []


def test_skew_above_timeout_half_merges_and_is_reported():
    # 40s and -45s both exceed timeout/2 = 30s: the merge must STILL be
    # causally ordered, and the verdict must say the skew was dangerous
    dumps = _skewed_pod(skew1_ns=40 * S, skew2_ns=-45 * S)
    entries, offsets, warnings, _ = blackbox.merge(dumps, timeout=TIMEOUT)
    assert [e["name"] for e in entries] == _TRUE_ORDER
    assert offsets[1] == pytest.approx(40 * S, abs=S // 100)
    assert sum("exceeds timeout/2" in w for w in warnings) == 2
    verdict = blackbox.analyze(dumps, timeout=TIMEOUT)
    assert (verdict["site"], verdict["kind"], verdict["rank"]) == \
        ("kvstore.kv", "dead_node", 2)
    assert any("exceeds timeout/2" in w for w in verdict["warnings"])
    assert "exceeds timeout/2" in blackbox.verdict_line(verdict)


def test_uncorrectable_skew_is_reported_in_the_verdict():
    # a host with neither heartbeat pairs nor shared generation events
    # cannot be aligned: it must be flagged, not silently mis-ordered
    dumps = _skewed_pod(5 * S, -9 * S)
    dumps.append(_dump(3, [(99 * S, "phase", "fwd", {"seconds": 0.1})]))
    verdict = blackbox.analyze(dumps, timeout=TIMEOUT)
    assert any("UNCORRECTABLE" in w and "host 3" in w
               for w in verdict["warnings"])
    assert "UNCORRECTABLE" in blackbox.verdict_line(verdict)


def test_generation_event_fallback_aligns_pairless_host():
    # no heartbeat stamps at all: two hosts sharing an elastic reshard
    # (generation bump) event still align on it
    h0 = _dump(0, [
        (1 * S, "elastic", "reshard", {"generation": 1}),
        (3 * S, "fault", "x/preempt",
         {"site": "x", "kind": "preempt", "rank": None}),
    ])
    h1 = _dump(1, [
        (1 * S + 7 * S, "elastic", "reshard", {"generation": 1}),
        (2 * S + 7 * S, "phase", "fwd", {"seconds": 0.1}),
    ])
    entries, offsets, warnings, _ = blackbox.merge([h0, h1],
                                                   timeout=TIMEOUT)
    assert offsets[1] == 7 * S
    assert [e["name"] for e in entries] == ["reshard", "reshard", "fwd",
                                            "x/preempt"]
    assert warnings == []


# ---------------------------------------------------------------------------
# verdicts
# ---------------------------------------------------------------------------

def test_fault_free_record_verdict_none():
    dumps = [_dump(h, [
        (h * S + 1 * S, "phase", "fwd", {"seconds": 0.01}),
        (h * S + 2 * S, "collective", "pushpull",
         {"seconds": 0.01, "bytes": 64}),
        (h * S + 3 * S, "checkpoint", "save",
         {"step": 1, "outcome": "written"}),
    ]) for h in range(3)]
    verdict = blackbox.analyze(dumps, timeout=TIMEOUT)
    assert verdict["verdict"] == "NONE"
    assert verdict["site"] is None and verdict["chain"] == []
    assert blackbox.verdict_line(verdict).startswith(
        "blackbox_verdict: NONE")


def test_dead_node_verdict_names_site_kind_rank_and_chain():
    verdict = blackbox.analyze(_skewed_pod(0, 0), timeout=TIMEOUT)
    assert verdict["verdict"] == "kvstore.kv/dead_node"
    assert (verdict["site"], verdict["kind"], verdict["rank"]) == \
        ("kvstore.kv", "dead_node", 2)
    assert verdict["terminal"]["name"] == "DeadNodeError"
    # the chain runs from the injection through the stale observation to
    # the terminal error
    assert [e["name"] for e in verdict["chain"]] == \
        ["kvstore.kv/dead_node", "observe", "DeadNodeError"]


def test_heartbeat_gap_is_the_root_cause_without_an_injection():
    # a real-world death has no "fault" event: the first stale liveness
    # observation is the earliest anomaly
    h0 = _dump(0, [
        (1 * S, "heartbeat", "observe",
         {"rank": 1, "stamp": None, "stale": True, "consecutive": 2}),
        (2 * S, "terminal", "DeadNodeError", {"dead_ranks": [1]}),
    ])
    verdict = blackbox.analyze([h0], timeout=TIMEOUT)
    assert (verdict["site"], verdict["kind"], verdict["rank"]) == \
        ("kvstore.kv", "heartbeat_gap", 1)


def test_non_finite_loss_verdict():
    h0 = _dump(0, [
        (1 * S, "sentinel", "divergence_trip",
         {"loss": None, "ema": 0.5, "finite": False}),
        (2 * S, "terminal", "DivergenceError", {"rollbacks": 3}),
    ])
    verdict = blackbox.analyze([h0], timeout=TIMEOUT)
    assert (verdict["site"], verdict["kind"]) == \
        ("train.loss", "non_finite_loss")


def test_overlapping_dumps_of_one_host_dedupe():
    base = [(1 * S, "phase", "fwd", {"seconds": 0.01}),
            (2 * S, "fault", "a/b", {"site": "a", "kind": "b",
                                     "rank": None})]
    d1 = _dump(0, base)
    d2 = _dump(0, base + [(3 * S, "terminal", "E", {})], step=3)
    verdict = blackbox.analyze([d1, d2], timeout=TIMEOUT)
    assert verdict["events"] == 3          # not 5
    assert verdict["verdict"] == "a/b"


# ---------------------------------------------------------------------------
# chrome trace + CLI
# ---------------------------------------------------------------------------

def test_chrome_trace_shape():
    entries, _, _, _ = blackbox.merge(_skewed_pod(0, 0), timeout=TIMEOUT)
    trace = blackbox.chrome_trace(entries)
    assert set(trace) == {"traceEvents"}
    evs = trace["traceEvents"]
    assert len(evs) == len(entries)
    assert {e["pid"] for e in evs} == {0, 1, 2}
    assert all(e["ph"] in ("X", "i") for e in evs)
    # spans carry durations; instants do not
    spans = [e for e in evs if e["ph"] == "X"]
    assert all("dur" in e for e in spans)


def test_cli_merges_and_prints_verdict(tmp_path, capsys):
    from tools.blackbox.__main__ import main
    paths = []
    for d in _skewed_pod(5 * S, -9 * S):
        p = tmp_path / f"blackbox-host{d['host']:05d}.json"
        p.write_text(json.dumps(d))
        paths.append(str(p))
    trace_file = tmp_path / "pod.trace.json"
    rc = main([str(tmp_path), "--timeline", "--trace", str(trace_file),
               "--timeout", str(TIMEOUT)])
    out = capsys.readouterr().out
    assert rc == 0
    assert "blackbox_verdict: ROOT-CAUSE kvstore.kv/dead_node rank=2" \
        in out
    assert "[fault] kvstore.kv/dead_node" in out       # timeline line
    with open(trace_file) as f:
        assert json.load(f)["traceEvents"]
    # a directory of dumps loads the same as explicit paths
    assert len(blackbox.load(str(tmp_path))) == 3


def test_a_dump_that_holds_span_events_still_merges(tmp_path):
    """`telemetry.span` writes whole spans into the ring (ISSUE 25): the dump
    keeps its schema, the analyzer reads them as it read phase events, and
    its chrome trace draws them as complete events ending at their stamp."""
    from mxnet_tpu import telemetry
    observe.reset(enabled=True)
    try:
        with telemetry.step_phase("eval", step=4):
            with telemetry.span("inner", cat="unit", shard=1):
                pass
        observe.record("checkpoint", "save", outcome="written")
        path = observe.dump(reason="unit", path=str(tmp_path / "blackbox-host00000.json"))
        spans = observe.spans()
    finally:
        observe.reset()
    doc = blackbox.load_dump(path)
    assert doc["schema"] == observe.SCHEMA_VERSION
    assert all(len(ev) == 7 for ev in doc["events"])
    entries, _offsets, warnings, dropped = blackbox.merge([doc], timeout=TIMEOUT)
    assert [(e["cat"], e["name"]) for e in entries] == [
        ("unit", "inner"), ("step_phase", "step/eval"), ("checkpoint", "save")]
    assert not warnings and not dropped
    assert blackbox.analyze([doc])["verdict"] == "NONE"
    inner = entries[0]["payload"]
    assert inner["parent"] == spans[1]["id"] and inner["step"] == 4
    assert inner["shard"] == 1 and inner["seconds"] >= 0
    drawn = blackbox.chrome_trace(entries)["traceEvents"]
    assert [e["ph"] for e in drawn] == ["X", "X", "i"]
    assert drawn[0]["dur"] == pytest.approx(inner["seconds"] * 1e6)


# -- signal-path audit (ISSUE 20 satellite) -----------------------------------

def test_sigterm_mid_run_dumps_then_terminates(tmp_path):
    """A real SIGTERM delivered mid-run: the handler itself only writes
    one byte to a pre-opened pipe (async-signal-safe); the deferred
    dumper thread records, dumps, then chains to the previous
    disposition (SIG_DFL here -> exit 128+15)."""
    import signal
    import subprocess
    import sys
    import time

    script = (
        "import os, time\n"
        "from mxnet_tpu.observe import flightrec\n"
        "assert flightrec.install_signal_handlers()\n"
        "flightrec.record('test', 'alive', pid=os.getpid())\n"
        "print('READY', flush=True)\n"
        "time.sleep(120)\n"
    )
    env = dict(os.environ, MXNET_BLACKBOX="1",
               MXNET_BLACKBOX_DIR=str(tmp_path), JAX_PLATFORMS="cpu")
    proc = subprocess.Popen([sys.executable, "-c", script], env=env,
                            stdout=subprocess.PIPE, text=True)
    try:
        assert proc.stdout.readline().strip() == "READY"
        proc.send_signal(signal.SIGTERM)
        rc = proc.wait(timeout=60)
    finally:
        if proc.poll() is None:
            proc.kill()
    assert rc == 128 + signal.SIGTERM       # chained to SIG_DFL
    dumps = [n for n in os.listdir(tmp_path) if n.endswith(".json")]
    assert len(dumps) == 1
    payload = json.load(open(tmp_path / dumps[0]))
    assert payload["reason"] == "signal%d" % signal.SIGTERM
    names = [(e[4], e[5]) for e in payload["events"]]
    assert ("test", "alive") in names
    assert ("terminal", "signal") in names  # recorded OFF-handler


def test_sigint_chains_to_callable_prev_handler(tmp_path, monkeypatch):
    """In-process SIGINT: the deferred dumper calls a callable previous
    handler (off the handler, on the worker thread) after dumping."""
    import signal
    import threading
    import time
    from mxnet_tpu.observe import flightrec

    monkeypatch.setenv("MXNET_BLACKBOX_DIR", str(tmp_path))
    seen = threading.Event()
    chained = []

    def prev_handler(signum, frame):
        chained.append(signum)
        seen.set()

    old_int = signal.getsignal(signal.SIGINT)
    old_term = signal.getsignal(signal.SIGTERM)
    old_installed = flightrec._signals_installed
    flightrec._signals_installed = False
    signal.signal(signal.SIGINT, prev_handler)
    try:
        assert flightrec.install_signal_handlers()
        os.kill(os.getpid(), signal.SIGINT)
        assert seen.wait(timeout=30)        # the chain actually ran
        assert chained == [signal.SIGINT]
        # the dump landed before the chain call
        deadline = time.time() + 10
        while time.time() < deadline and not os.listdir(tmp_path):
            time.sleep(0.05)
        dumps = [n for n in os.listdir(tmp_path) if n.endswith(".json")]
        assert dumps
        payload = json.load(open(tmp_path / dumps[0]))
        assert payload["reason"] == "signal%d" % signal.SIGINT
    finally:
        signal.signal(signal.SIGINT, old_int)
        signal.signal(signal.SIGTERM, old_term)
        flightrec._signals_installed = old_installed
