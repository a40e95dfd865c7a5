"""Telemetry layer tests: metrics registry (labels/buckets/exposition),
step-trace spans, retrace watchdog, and the publisher integrations
(trainer, kvstore tpu_ici, serve) — ISSUE 2."""
import json
import logging
import re
import sys
import threading

import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import profiler, telemetry
from mxnet_tpu.gluon import nn


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

def test_registry_counter_labels():
    reg = telemetry.MetricsRegistry()
    c = reg.counter("req_total", "requests", ("endpoint", "event"))
    c.labels(endpoint="a", event="ok").inc()
    c.labels(endpoint="a", event="ok").inc(2)
    c.labels("a", "err").inc()
    assert c.labels(endpoint="a", event="ok").value == 3
    assert reg.get_sample_value(
        "req_total", {"endpoint": "a", "event": "err"}) == 1
    # unknown combination reads as absent
    assert reg.get_sample_value(
        "req_total", {"endpoint": "b", "event": "ok"}) is None
    with pytest.raises(ValueError):
        c.inc()          # labeled family needs .labels()
    with pytest.raises(ValueError):
        c.labels(endpoint="a").inc()   # missing label
    with pytest.raises(ValueError):
        c.labels(endpoint="a", event="ok").inc(-1)  # counters go up


def test_registry_gauge_and_reregistration():
    reg = telemetry.MetricsRegistry()
    g = reg.gauge("depth", "queue depth")
    g.set(5)
    g.dec(2)
    assert g.value == 3
    # get-or-create returns the same family; kind mismatch raises
    assert reg.gauge("depth") is g
    with pytest.raises(ValueError):
        reg.counter("depth")


def test_registry_histogram_buckets():
    reg = telemetry.MetricsRegistry()
    h = reg.histogram("lat_seconds", "latency", buckets=(0.01, 0.1, 1.0))
    for v in (0.005, 0.05, 0.5, 5.0):
        h.observe(v)
    # cumulative bucket semantics: le is inclusive
    assert reg.get_sample_value("lat_seconds_bucket", {"le": "0.01"}) == 1
    assert reg.get_sample_value("lat_seconds_bucket", {"le": "0.1"}) == 2
    assert reg.get_sample_value("lat_seconds_bucket", {"le": "1"}) == 3
    assert reg.get_sample_value("lat_seconds_bucket", {"le": "+Inf"}) == 4
    assert reg.get_sample_value("lat_seconds_count", {}) == 4
    assert reg.get_sample_value("lat_seconds_sum", {}) == \
        pytest.approx(5.555)
    # an observation exactly on a bound lands in that bucket
    h.observe(0.1)
    assert reg.get_sample_value("lat_seconds_bucket", {"le": "0.1"}) == 3


_PROM_LINE = re.compile(
    r'^([a-zA-Z_:][a-zA-Z0-9_:]*)(?:\{(.*)\})?\s([-+0-9.eE]+|[+-]Inf)$')


def _parse_prometheus(text):
    """{(sample_name, frozenset(label items)): value}"""
    out = {}
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        m = _PROM_LINE.match(line)
        assert m, f"unparseable exposition line: {line!r}"
        name, labels, value = m.groups()
        items = frozenset(
            tuple(kv.split("=", 1)) for kv in labels.split(",")) \
            if labels else frozenset()
        items = frozenset((k, v.strip('"')) for k, v in items)
        out[(name, items)] = float(value)
    return out


def test_exposition_roundtrip():
    """Prometheus text and JSON exposition carry the same samples."""
    reg = telemetry.MetricsRegistry()
    reg.counter("a_total", 'with "quotes" and \\slash', ("k",)) \
        .labels(k='va"l').inc(7)
    reg.gauge("b").set(-2.5)
    h = reg.histogram("c_seconds", "h", ("p",), buckets=(0.5,))
    h.labels(p="x").observe(0.25)
    h.labels(p="x").observe(2.0)

    prom = _parse_prometheus(reg.export_prometheus())
    doc = json.loads(reg.export_json())
    json_samples = {}
    for fam in doc["metrics"]:
        for s in fam["samples"]:
            key = (s["name"], frozenset(
                (k, str(v)) for k, v in s["labels"].items()))
            json_samples[key] = float(s["value"])
    # every prom sample appears in json with the same value (label
    # escaping differs textually, so compare the unescaped json side by
    # count + spot values)
    assert len(prom) == len(json_samples)
    assert json_samples[("b", frozenset())] == -2.5
    assert json_samples[("c_seconds_bucket",
                         frozenset({("p", "x"), ("le", "0.5")}))] == 1
    assert json_samples[("c_seconds_count", frozenset({("p", "x")}))] == 2
    assert prom[("b", frozenset())] == -2.5


def _unescape_label_value(v):
    """Invert text-format 0.0.4 label-value escaping (\\\\, \\", \\n)."""
    out, i = [], 0
    while i < len(v):
        c = v[i]
        if c == "\\" and i + 1 < len(v):
            n = v[i + 1]
            out.append({"n": "\n", "\\": "\\", '"': '"'}.get(n, c + n))
            i += 2
        else:
            out.append(c)
            i += 1
    return "".join(out)


def test_exposition_hostile_label_values_roundtrip():
    """Backslashes, double quotes, and newlines in label VALUES must be
    escaped per the Prometheus text format and parse back to the exact
    original strings — no label may break the line-oriented exposition
    (ISSUE 17 satellite)."""
    hostile = [
        "back\\slash", 'quo"te', "new\nline",
        'all\\three" \n mixed', "\\n literal backslash-n",
        "trailing backslash\\", '"', "\n", "\\",
        'fake closer"} 9',
    ]
    reg = telemetry.MetricsRegistry()
    c = reg.counter("hostile_total", "hostile labels", ("v",))
    for i, val in enumerate(hostile):
        c.labels(v=val).inc(i + 1)
    text = reg.export_prometheus()
    # line-oriented: raw newlines inside values never split a sample
    sample_lines = [ln for ln in text.splitlines()
                    if ln.startswith("hostile_total{")]
    assert len(sample_lines) == len(hostile)
    got = {}
    prefix = 'hostile_total{v="'
    for line in sample_lines:
        assert line.startswith(prefix), line
        escaped, value = line[len(prefix):].rsplit('"} ', 1)
        assert "\n" not in escaped
        got[_unescape_label_value(escaped)] = float(value)
    assert got == {val: float(i + 1) for i, val in enumerate(hostile)}
    # and the registry reads every hostile combination back untouched
    for i, val in enumerate(hostile):
        assert reg.get_sample_value("hostile_total", {"v": val}) == i + 1


def test_registry_thread_safety():
    reg = telemetry.MetricsRegistry()
    c = reg.counter("n_total")
    h = reg.histogram("n_seconds", buckets=(0.5,))

    def work():
        for _ in range(20000):
            c.inc()
            h.observe(0.1)

    threads = [threading.Thread(target=work) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value == 80000
    assert reg.get_sample_value("n_seconds_count", {}) == 80000


# ---------------------------------------------------------------------------
# retrace / compile watchdog
# ---------------------------------------------------------------------------

def test_watchdog_flags_forced_rejit(caplog):
    import jax
    import jax.numpy as jnp

    reg = telemetry.MetricsRegistry()
    wd = telemetry.RetraceWatchdog(steady_after=1, registry=reg)
    f = wd.watch(jax.jit(lambda x: x * 2), name="double")
    f(jnp.ones((3,)))          # first compile: expected, not a retrace
    f(jnp.ones((3,)))          # cached
    assert wd.retrace_count("double") == 0
    with caplog.at_level(logging.WARNING, logger="mxnet_tpu.telemetry"):
        f(jnp.ones((4,)))      # shape drift past steady state -> re-jit
    assert wd.retrace_count("double") == 1
    assert reg.get_sample_value(
        "mxtpu_jit_retrace_total", {"fn": "double"}) == 1
    warnings = [r for r in caplog.records if "double" in r.getMessage()]
    assert warnings and "recompile" in warnings[0].getMessage()


def test_watchdog_quiet_before_steady_state(caplog):
    import jax
    import jax.numpy as jnp

    reg = telemetry.MetricsRegistry()
    wd = telemetry.RetraceWatchdog(steady_after=5, registry=reg)
    f = wd.watch(jax.jit(lambda x: x + 1), name="warming")
    with caplog.at_level(logging.WARNING, logger="mxnet_tpu.telemetry"):
        for n in (2, 3, 4):    # warmup sweep: counted, never warned
            f(jnp.ones((n,)))
    assert wd.retrace_count("warming") == 2
    assert not [r for r in caplog.records if "warming" in r.getMessage()]


def test_compile_listener_counts_xla_compiles():
    import jax
    import jax.numpy as jnp

    reg = telemetry.default_registry()

    def count():
        return reg.get_sample_value(
            "mxtpu_xla_compile_total", {"stage": "compile"}) or 0

    before = count()
    jax.jit(lambda x: x * 3.5 + 1)(jnp.ones((5,)))   # fresh fn: must compile
    assert count() >= before + 1
    assert (reg.get_sample_value(
        "mxtpu_xla_compile_seconds_count", {"stage": "compile"}) or 0) > 0


def test_hybrid_block_observed_by_default_watchdog():
    net = nn.Dense(3)
    net.initialize()
    net.hybridize()
    name = "Dense.hybrid_forward"
    before = telemetry.default_registry().get_sample_value(
        "mxtpu_jit_retrace_total", {"fn": name}) or 0
    net(mx.np.ones((2, 4)))
    net(mx.np.ones((2, 4)))     # steady
    net(mx.np.ones((6, 4)))     # batch-shape drift forces a re-trace
    after = telemetry.default_registry().get_sample_value(
        "mxtpu_jit_retrace_total", {"fn": name}) or 0
    assert after >= before + 1


# ---------------------------------------------------------------------------
# step-trace spans + trainer phases
# ---------------------------------------------------------------------------

def _train_3_steps(hybridize=True):
    net = nn.Dense(4)
    net.initialize()
    if hybridize:
        net.hybridize()
    trainer = mx.gluon.Trainer(net.collect_params(), "sgd",
                               {"learning_rate": 0.1})
    x = mx.np.array(onp.random.randn(2, 3).astype(onp.float32))
    for _ in range(3):
        with mx.autograd.record():
            loss = (net(x) ** 2).mean()
        loss.backward()
        trainer.step(2)
    return net, x


def test_trainer_step_phases_in_trace():
    profiler.dumps(reset=True)
    profiler.set_state("run")
    _train_3_steps(hybridize=True)
    profiler.set_state("stop")
    events = json.loads(profiler.dumps(format="json", reset=True))[
        "traceEvents"]
    phases = {e["name"] for e in events if e.get("cat") == "step_phase"}
    assert {"step/fwd", "step/bwd", "step/allreduce",
            "step/optimizer"} <= phases
    # op events share the same timeline (the hybrid forward dispatch)
    assert any(e.get("cat") == "operator" for e in events)
    # 3 steps -> at least 3 spans per phase
    fwd = [e for e in events if e.get("name") == "step/fwd"]
    assert len(fwd) >= 3 and all(e.get("dur", 0) >= 0 for e in fwd)
    # while profiling, op dispatches also publish into the registry
    assert "mxtpu_ops_dispatched_total{" in telemetry.export_prometheus()


def test_step_phase_histogram_published():
    before = telemetry.default_registry().get_sample_value(
        "mxtpu_trainer_step_phase_seconds_count", {"phase": "optimizer"}) or 0
    _train_3_steps(hybridize=False)
    after = telemetry.default_registry().get_sample_value(
        "mxtpu_trainer_step_phase_seconds_count", {"phase": "optimizer"})
    assert after == before + 3
    text = telemetry.export_prometheus()
    assert 'mxtpu_trainer_step_phase_seconds_bucket{phase="optimizer"' \
        in text


def test_dataloader_data_wait_phase():
    from mxnet_tpu.gluon.data import ArrayDataset, DataLoader

    ds = ArrayDataset(onp.arange(32, dtype=onp.float32).reshape(8, 4))
    loader = DataLoader(ds, batch_size=4)
    before = telemetry.default_registry().get_sample_value(
        "mxtpu_trainer_step_phase_seconds_count", {"phase": "data-wait"}) or 0
    assert len(list(loader)) == 2
    after = telemetry.default_registry().get_sample_value(
        "mxtpu_trainer_step_phase_seconds_count", {"phase": "data-wait"})
    assert after == before + 2


# ---------------------------------------------------------------------------
# kvstore collectives
# ---------------------------------------------------------------------------

def test_tpu_ici_collective_counters():
    kv = mx.kv.create("tpu_ici")
    reg = telemetry.default_registry()
    n_before = reg.get_sample_value(
        "mxtpu_kvstore_collective_total", {"op": "allreduce"}) or 0
    b_before = reg.get_sample_value(
        "mxtpu_kvstore_collective_bytes_total", {"op": "allreduce"}) or 0
    vals = [mx.np.ones((4, 4), ctx=mx.cpu(i)) for i in range(4)]
    kv.pushpull(0, vals)
    assert reg.get_sample_value(
        "mxtpu_kvstore_collective_total", {"op": "allreduce"}) == n_before + 1
    # 4 copies x 16 f32 = 256 payload bytes
    assert reg.get_sample_value(
        "mxtpu_kvstore_collective_bytes_total",
        {"op": "allreduce"}) == b_before + 256
    assert (reg.get_sample_value(
        "mxtpu_kvstore_collective_seconds_count", {"op": "allreduce"}) or 0) \
        >= n_before + 1


def test_tpu_ici_collective_span_in_trace():
    kv = mx.kv.create("tpu_ici")
    profiler.dumps(reset=True)
    profiler.set_state("run")
    vals = [mx.np.ones((2, 2), ctx=mx.cpu(i)) for i in range(2)]
    kv.pushpull(1, vals)
    profiler.set_state("stop")
    events = json.loads(profiler.dumps(format="json", reset=True))[
        "traceEvents"]
    spans = [e for e in events if e.get("cat") == "collective"]
    assert spans and spans[0]["name"] == "collective/allreduce"
    assert spans[0]["args"]["bytes"] == 2 * 2 * 2 * 4


# ---------------------------------------------------------------------------
# the one span primitive and its record (ISSUE 25)
# ---------------------------------------------------------------------------

# the module: `telemetry.watchdog` is the function of the same name
_WATCHDOG = sys.modules["mxnet_tpu.telemetry.watchdog"]


@pytest.fixture
def record(monkeypatch):
    """A clean, whole span record for one test (the ring is the process's),
    with every outermost trace on it, however short."""
    from mxnet_tpu import observe
    monkeypatch.setattr(_WATCHDOG, "_TRACE_SPAN_FLOOR_S", 0.0)
    observe.reset(enabled=True)
    yield observe
    observe.reset()


def _toy_fused_step(dtype="float32"):
    """Dense(1) under SGD momentum in one FusedTrainStep.  In bfloat16 the
    momentum state is made bf16 and comes back f32 from the first update,
    as in ResNet-50's cell, so the step compiles twice."""
    net = nn.Dense(1)
    net.initialize()
    net.cast(dtype)

    class WithLoss(mx.gluon.HybridBlock):
        def __init__(self, net):
            super().__init__()
            self.net = net

        def forward(self, x, y):
            return ((self.net(x) - y) ** 2).mean()

    trainer = mx.gluon.Trainer(net.collect_params(), "sgd",
                               {"learning_rate": 0.1, "momentum": 0.9})
    fused = mx.gluon.FusedTrainStep(WithLoss(net), trainer)
    x = mx.np.ones((4, 3), dtype=dtype)
    y = mx.np.ones((4, 1), dtype=dtype)
    return fused, (x, y)


def test_span_writes_one_event_with_begin_end_parent_and_step(record):
    with telemetry.span("outer", cat="unit", step=7, shard=3) as outer:
        with telemetry.span("inner", cat="unit") as inner:
            inner.args["found"] = True
    events = [e for e in record.events() if e[4] == "unit"]
    assert [e[5] for e in events] == ["inner", "outer"]    # one each, at exit
    got = {s["name"]: s for s in record.spans()}
    assert set(got) == {"inner", "outer"} and record.spans().dropped == 0
    for s in got.values():
        assert s["begin_ns"] <= s["end_ns"] and s["cat"] == "unit"
    assert got["outer"]["parent"] is None and got["outer"]["step"] == 7
    assert got["inner"]["parent"] == got["outer"]["id"] == outer.id
    assert got["inner"]["step"] == 7                       # its parent's
    assert got["outer"]["args"] == {"shard": 3}
    assert got["inner"]["args"] == {"found": True}
    # nested in time as well as by id, and the event's stamp is the end
    assert got["outer"]["begin_ns"] <= got["inner"]["begin_ns"]
    assert got["inner"]["end_ns"] <= got["outer"]["end_ns"] == events[1][0]
    assert record.spans("inner") == [got["inner"]]
    assert outer.seconds == pytest.approx(events[1][6]["seconds"])


def test_span_survives_an_exception_and_leaves_no_parent_behind(record):
    with pytest.raises(KeyError):
        with telemetry.span("fails", cat="unit"):
            raise KeyError("x")
    with telemetry.span("after", cat="unit"):
        pass
    got = {s["name"]: s for s in record.spans()}
    assert got["fails"]["begin_ns"] <= got["fails"]["end_ns"]
    assert got["after"]["parent"] is None


def test_a_second_threads_spans_do_not_take_the_firsts_parent(record):
    inside = threading.Event()
    release = threading.Event()

    def other():
        inside.wait(10)
        with telemetry.span("other-thread", cat="unit"):
            pass
        release.set()

    t = threading.Thread(target=other)
    t.start()
    with telemetry.span("main-thread", cat="unit"):
        inside.set()
        assert release.wait(10)
    t.join(10)
    assert not t.is_alive()
    got = {s["name"]: s for s in record.spans()}
    assert got["other-thread"]["parent"] is None
    assert got["other-thread"]["step"] is None
    assert got["main-thread"]["begin_ns"] <= got["other-thread"]["begin_ns"]


def test_fused_step_spans_nest_and_share_the_step(record):
    fused, args = _toy_fused_step()
    for _ in range(3):
        fused(*args, batch_size=4)
    steps = record.spans("fused_step.step")
    assert [s["step"] for s in steps] == [1, 2, 3]
    assert all(s["parent"] is None and s["cat"] == "step_phase" for s in steps)
    for step in steps:
        kids = [s for s in record.spans() if s["parent"] == step["id"]]
        assert [k["name"] for k in sorted(kids, key=lambda k: k["begin_ns"])] \
            == ["fused_step.prepare", "fused_step.launch"]
        assert all(k["step"] == step["step"] for k in kids)
        assert all(step["begin_ns"] <= k["begin_ns"] <= k["end_ns"]
                   <= step["end_ns"] for k in kids)
    # a step leaves three events and no more: itself and its two parts
    names = [s["name"] for s in record.spans() if s["step"] == 3]
    assert sorted(names) == ["fused_step.launch", "fused_step.prepare",
                             "fused_step.step"]


def test_fused_step_prepare_outside_a_step_has_no_parent(record):
    fused, args = _toy_fused_step()
    fused.lower(*args, batch_size=4)
    (prepare,) = record.spans("fused_step.prepare")
    assert prepare["parent"] is None and prepare["step"] is None
    assert record.spans("fused_step.step") == []


@pytest.mark.parametrize("dtype,compiles", [("float32", 1), ("bfloat16", 2)])
def test_launch_says_which_calls_compiled(record, dtype, compiles):
    """bf16 momentum comes back f32 from the first update: exactly two
    launches compile, the first one (no retrace to the watchdog) included."""
    fused, args = _toy_fused_step(dtype)
    retraces = telemetry.watchdog().retrace_count("FusedTrainStep[WithLoss]")
    for _ in range(5):
        fused(*args, batch_size=4)
    launches = record.spans("fused_step.launch")
    compiled = [s["args"]["compiled"] for s in launches]
    assert compiled == [True] * compiles + [False] * (5 - compiles)
    # the flag is the watchdog's own reading, which counts all but the first
    assert telemetry.watchdog().retrace_count("FusedTrainStep[WithLoss]") \
        == retraces + compiles - 1
    # each compiling launch holds the step program's three xla stages
    for launch in launches[:compiles]:
        stages = sorted(s["name"] for s in record.spans()
                        if s["parent"] == launch["id"]
                        and "fused" in s["args"].get("fun_name", ""))
        assert stages == ["xla.compile", "xla.lower", "xla.trace"]


def test_xla_spans_carry_fun_name_and_only_the_outermost_trace(record):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def inner_fn(x):
        return x * 2.5

    def outer_fn(x):
        return inner_fn(x) + inner_fn(x + 1)

    x = jnp.ones((7,))       # its own small program, outside the span
    with telemetry.span("compiling", cat="unit", step=11) as sp:
        jax.jit(outer_fn)(x)
    mine = [s for s in record.spans() if s["parent"] == sp.id]
    assert sorted(s["name"] for s in mine) == \
        ["xla.compile", "xla.lower", "xla.trace"]
    assert all("outer_fn" in s["args"]["fun_name"] for s in mine)
    assert all(s["step"] == 11 and s["cat"] == "compile" for s in mine)
    assert all(sp.begin_ns <= s["begin_ns"] <= s["end_ns"] for s in mine)
    (compile_,) = [s for s in mine if s["name"] == "xla.compile"]
    assert compile_["args"]["cache_hit"] is False
    # the counters still see every trace, the nested ones too
    assert not any("inner_fn" in s["args"].get("fun_name", "")
                   for s in record.spans("xla.trace"))


def test_a_short_trace_is_counted_and_takes_no_slot(record, monkeypatch):
    """Eager ops retrace by the hundred; what they compile stays on record."""
    import jax
    import jax.numpy as jnp

    monkeypatch.setattr(_WATCHDOG, "_TRACE_SPAN_FLOOR_S", float("inf"))
    traces = lambda: telemetry.default_registry().get_sample_value(
        "mxtpu_xla_compile_total", {"stage": "trace"}) or 0
    before = traces()
    jax.jit(lambda x: x * 3.5 - 2)(jnp.ones((11,)))
    assert traces() > before and record.spans("xla.trace") == []
    assert len(record.spans("xla.lower")) == len(record.spans("xla.compile")) >= 1


def _host_plane_events(tmp_path, body):
    """Run `body` under a CPU `jax.profiler` session; [(name, start, end)] of
    the host planes of the `.xplane.pb` it leaves."""
    import glob
    import os

    import jax
    from jax.profiler import ProfileData

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(
        str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))
    return [(e.name, e.start_ns, e.end_ns)
            for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events]


def test_xplane_host_plane_holds_the_programs_spans(record, tmp_path):
    fused, args = _toy_fused_step()
    fused(*args, batch_size=4)          # compile outside the session
    events = _host_plane_events(
        tmp_path, lambda: [fused(*args, batch_size=4) for _ in range(2)])
    by_name = {}
    for name, start, end in events:
        by_name.setdefault(name, []).append((start, end))
    assert len(by_name["fused_step.step"]) == 2
    for name in ("fused_step.prepare", "fused_step.launch"):
        assert len(by_name[name]) == 2
        for (s0, s1), (c0, c1) in zip(sorted(by_name["fused_step.step"]),
                                      sorted(by_name[name])):
            assert s0 <= c0 <= c1 <= s1
    # the same two steps are on the record, on its own clock
    assert [s["step"] for s in record.spans("fused_step.step")] == [1, 2, 3]


def test_recorder_off_spans_still_annotate_and_record_nothing(tmp_path):
    from mxnet_tpu import observe
    observe.reset(enabled=False)
    try:
        def body():
            with telemetry.step_phase("eval") as phase:
                pass
            assert phase.begin_ns <= phase.end_ns     # the histogram's reading
        before = telemetry.default_registry().get_sample_value(
            "mxtpu_trainer_step_phase_seconds_count", {"phase": "eval"}) or 0
        events = _host_plane_events(tmp_path, body)
        assert "step/eval" in {name for name, _s, _e in events}
        assert observe.spans() == [] and observe.events() == []
        assert telemetry.default_registry().get_sample_value(
            "mxtpu_trainer_step_phase_seconds_count",
            {"phase": "eval"}) == before + 1
    finally:
        observe.reset()


def test_wrappers_go_through_the_one_span(record):
    """step_phase, collective_span and the serve batch span are span events
    of their own category; the serve worker's has no parent here."""
    net, x = _train_3_steps(hybridize=False)
    kv = mx.kv.create("tpu_ici")
    kv.pushpull(3, [mx.np.ones((2, 2), ctx=mx.cpu(i)) for i in range(2)])
    ep = net.as_endpoint(max_batch_size=4, max_latency_ms=2)
    try:
        with telemetry.span("caller", cat="unit"):
            ep.predict(x)
    finally:
        ep.shutdown(drain=True)
    spans = record.spans()
    by_cat = {}
    for s in spans:
        by_cat.setdefault(s["cat"], []).append(s)
    assert {"step/fwd", "step/bwd", "step/optimizer"} <= \
        {s["name"] for s in by_cat["step_phase"]}
    (coll,) = [s for s in by_cat["collective"]
               if s["name"] == "collective/allreduce"]
    assert coll["args"] == {"op": "allreduce", "bytes": 2 * 2 * 2 * 4}
    (batch,) = by_cat["serve"]
    assert batch["name"] == f"serve/{ep.name}/batch" and batch["parent"] is None
    assert batch["args"]["rows"] == 2


def test_fused_step_histogram_observes_the_whole_step(record):
    reg = telemetry.default_registry()

    def read(suffix):
        return reg.get_sample_value(
            "mxtpu_trainer_step_phase_seconds_" + suffix,
            {"phase": "fused-step"}) or 0
    fused, args = _toy_fused_step()
    count, total = read("count"), read("sum")
    fused(*args, batch_size=4)
    (step,) = record.spans("fused_step.step")
    assert read("count") == count + 1
    assert read("sum") - total == pytest.approx(
        (step["end_ns"] - step["begin_ns"]) * 1e-9)


def test_chrome_events_are_stamped_from_the_records_clock(record):
    profiler.dumps(reset=True)
    profiler.set_state("run")
    with telemetry.span("both", cat="unit", k=1):
        pass
    marker = profiler._now_us()
    profiler.set_state("stop")
    (ev,) = [e for e in json.loads(profiler.dumps(format="json", reset=True))
             ["traceEvents"] if e["name"] == "both"]
    (sp,) = record.spans("both")
    assert ev["ts"] == pytest.approx(sp["begin_ns"] / 1e3)
    assert ev["dur"] == pytest.approx((sp["end_ns"] - sp["begin_ns"]) / 1e3)
    assert ev["args"] == {"k": 1} and ev["ts"] + ev["dur"] <= marker


def test_exhausted_dataloader_probe_leaves_no_span_open(record):
    data = mx.gluon.data.ArrayDataset(onp.arange(6, dtype="float32"))
    loader = mx.gluon.data.DataLoader(data, batch_size=3)
    assert len(list(loader)) == 2
    assert len(record.spans("step/data-wait")) == 2    # the probe is discarded
    with telemetry.span("next", cat="unit"):
        pass
    assert record.spans("next")[0]["parent"] is None


def test_spans_reports_what_the_ring_dropped():
    from mxnet_tpu.observe import FlightRecorder
    rec = FlightRecorder(capacity=4, enabled=True)
    # set-up is over (its spans are kept beside the ring: see below)
    rec.record_span("step_phase", "fused_step.step", begin_ns=0, end_ns=1,
                    id=100, parent=None, step=1)
    for i in range(6):
        rec.record_span("unit", f"s{i}", begin_ns=1 + i, id=i, parent=None,
                        step=None)
    got = rec.spans()
    assert [s["name"] for s in got] == \
        ["fused_step.step", "s2", "s3", "s4", "s5"]
    assert got.dropped == 2 and got.setup_dropped == 0
    # a span reported after the fact keeps its own end
    rec.record_span("unit", "late", begin_ns=10, end_ns=25, id=9,
                    parent=None, step=None)
    assert rec.spans("late")[0]["end_ns"] == 25


# ---------------------------------------------------------------------------
# serve integration
# ---------------------------------------------------------------------------

def test_serve_series_in_registry():
    net = nn.Dense(4)
    net.initialize()
    ep = net.as_endpoint(max_batch_size=4, max_latency_ms=2)
    try:
        out = ep.predict(mx.np.ones((2, 3)))
        assert out.shape == (2, 4)
    finally:
        ep.shutdown(drain=True)
    reg = telemetry.default_registry()
    labels = {"endpoint": ep.name, "event": "completed"}
    assert reg.get_sample_value("mxtpu_serve_requests_total", labels) == 1
    assert reg.get_sample_value(
        "mxtpu_serve_latency_seconds_count", {"endpoint": ep.name}) == 1
    assert reg.get_sample_value(
        "mxtpu_serve_batch_rows_total",
        {"endpoint": ep.name, "kind": "real"}) == 2
    text = telemetry.export_prometheus()
    assert f'mxtpu_serve_batches_total{{endpoint="{ep.name}"}}' in text


# ---------------------------------------------------------------------------
# the acceptance scenario: ONE dump interleaves every source
# ---------------------------------------------------------------------------

def test_unified_trace_one_dump(tmp_path):
    profiler.dumps(reset=True)
    f = str(tmp_path / "unified.json")
    profiler.set_config(filename=f)
    profiler.set_state("run")

    net, x = _train_3_steps(hybridize=True)           # step phases + ops
    kv = mx.kv.create("tpu_ici")
    kv.pushpull(0, [mx.np.ones((4,), ctx=mx.cpu(i)) for i in range(2)])
    ep = net.as_endpoint(max_batch_size=4, max_latency_ms=2)
    try:
        ep.predict(x)                                  # serve dispatch
    finally:
        ep.shutdown(drain=True)

    profiler.dump()            # finished=True: stops + writes + resets
    assert profiler.state() == "stop"
    events = json.load(open(f))["traceEvents"]
    cats = {e.get("cat") for e in events}
    assert {"step_phase", "operator", "collective", "serve"} <= cats
    serve_spans = [e for e in events if e.get("cat") == "serve"]
    assert serve_spans[0]["args"]["rows"] == 2
    # the dump reset the shared buffer: a fresh dumps() is empty
    assert json.loads(profiler.dumps(format="json"))["traceEvents"] == []
    # registry covers trainer + kvstore + serve series in one scrape
    text = telemetry.export_prometheus()
    for series in ("mxtpu_trainer_step_phase_seconds",
                   "mxtpu_kvstore_collective_total",
                   "mxtpu_serve_requests_total",
                   "mxtpu_xla_compile_total"):
        assert series in text, series


# ---------------------------------------------------------------------------
# profiler satellites
# ---------------------------------------------------------------------------

def test_profiler_counter_concurrent_increments():
    """increment/decrement are read-modify-write: without the lock,
    concurrent serve threads lose updates."""
    c = profiler.Domain("unit").new_counter("hits", 0)

    def work():
        for _ in range(30000):
            c.increment()

    threads = [threading.Thread(target=work) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value == 120000
    c.decrement(120000)
    assert c.value == 0


def test_profiler_scope_enter_failure_leaves_no_dangling_span(monkeypatch):
    class Boom:
        def __init__(self, name):
            pass

        def __enter__(self):
            raise RuntimeError("annotation unavailable")

        def __exit__(self, *exc):
            return False

    import jax
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Boom)
    profiler.dumps(reset=True)
    profiler.set_state("run")
    sc = profiler.scope("doomed")
    with pytest.raises(RuntimeError):
        sc.__enter__()
    sc.__exit__(None, None, None)     # must not raise nor emit
    profiler.set_state("stop")
    events = json.loads(profiler.dumps(format="json", reset=True))[
        "traceEvents"]
    assert not any(e.get("name") == "doomed" for e in events)


def test_profiler_dump_not_finished_keeps_state(tmp_path):
    profiler.dumps(reset=True)
    profiler.set_config(filename=str(tmp_path / "flush.json"))
    profiler.set_state("run")
    with profiler.scope("keep-me"):
        pass
    profiler.dump(finished=False)     # periodic flush: stays running
    assert profiler.state() == "run"
    with profiler.scope("second"):
        pass
    profiler.set_state("stop")
    events = json.loads(profiler.dumps(format="json", reset=True))[
        "traceEvents"]
    names = {e["name"] for e in events}
    assert {"keep-me", "second"} <= names   # buffer was not reset


# ---------------------------------------------------------------------------
# monitor satellites
# ---------------------------------------------------------------------------

def test_monitor_toc_print_fixed_precision(caplog):
    from mxnet_tpu.monitor import Monitor

    net = nn.Dense(2)
    net.initialize()
    mon = Monitor(interval=1).install(net)
    mon.tic()
    net(mx.np.ones((1, 3)))
    with caplog.at_level(logging.INFO):
        mon.toc_print()
    stats = [r.getMessage() for r in caplog.records
             if r.getMessage().startswith("Batch:")]
    assert stats
    for line in stats:
        assert re.search(r"\d+\.\d{6}$", line), line
    mon.uninstall()


def test_block_children_public_iteration():
    net = nn.HybridSequential()
    net.add(nn.Dense(4), nn.Dense(2))
    kids = net.children
    assert isinstance(kids, dict) and len(kids) == 2
    assert all(isinstance(c, mx.gluon.Block) for c in kids.values())
    # Monitor.install walks through the public surface
    from mxnet_tpu.monitor import Monitor
    net.initialize()
    mon = Monitor(interval=1).install(net)
    mon.tic()
    net(mx.np.ones((1, 3)))
    names = {n for _s, n, _v in mon.toc()}
    assert any(".0_output" in n for n in names)
    mon.uninstall()


# ---------------------------------------------------------------------------
# set-up on the span record: process start to the first dispatched step
# ---------------------------------------------------------------------------

_IMPORT_AND_PRINT = """
import json
{before}
import mxnet_tpu
from mxnet_tpu import observe
print(json.dumps(observe.spans()))
"""


@pytest.mark.parametrize("before,already_up", [
    ("", False), ("import jax; jax.devices()", True)])
def test_the_import_leaves_its_three_spans(before, already_up):
    """In a process of its own: the record starts with the process."""
    import subprocess
    done = subprocess.run(
        [sys.executable, "-c", _IMPORT_AND_PRINT.format(before=before)],
        capture_output=True, text=True, timeout=300)
    assert done.returncode == 0, done.stderr[-2000:]
    spans = json.loads(done.stdout.strip().splitlines()[-1])
    by_name = {}
    for s in spans:
        by_name.setdefault(s["name"], []).append(s)
    (before_import,) = by_name["process.before_import"]
    (imported,) = by_name["runtime.import"]
    (started,) = by_name["runtime.backend_start"]
    assert {s["cat"] for s in (before_import, imported, started)} == {"setup"}
    assert before_import["parent"] is None and imported["parent"] is None
    assert before_import["args"] == {} == imported["args"]
    # process start, then the package's first line, then its last
    assert before_import["begin_ns"] < before_import["end_ns"] == \
        imported["begin_ns"] < imported["end_ns"]
    assert (before_import["end_ns"] - before_import["begin_ns"]) < 120e9
    # the package's first touch of the backend is inside its import
    assert started["parent"] == imported["id"]
    assert imported["begin_ns"] <= started["begin_ns"] <= started["end_ns"] \
        <= imported["end_ns"]
    assert started["args"] == {"platform": "cpu", "devices": 8,
                               "already_up": already_up}
    # nothing compiled before the span that starts the runtime, unless the
    # host script had started it
    first_xla = min(s["begin_ns"] for s in spans if s["name"].startswith("xla."))
    assert already_up or first_xla >= started["begin_ns"]


def test_process_start_is_on_the_spans_clock_or_none(monkeypatch):
    import builtins
    import time
    start = telemetry.process_start_ns()
    assert start is not None and 0 < time.monotonic_ns() - start < 3600e9 * 24
    assert abs(telemetry.process_start_ns() - start) < 50e6     # to a tick or two

    def no_proc(path, *a, **kw):
        raise FileNotFoundError(path)
    monkeypatch.setattr(builtins, "open", no_proc)
    assert telemetry.process_start_ns() is None


class _Deferred(mx.gluon.HybridBlock):
    """Two Dense layers whose input widths wait for the first forward."""

    def __init__(self):
        super().__init__()
        self.a = nn.Dense(5)
        self.b = nn.Dense(2)

    def forward(self, x):
        return self.b(self.a(x))


def test_settling_deferred_shapes_is_one_span_and_only_when_pending(record):
    net = _Deferred()
    net.initialize()
    pending = sum(p._deferred_init is not None
                  for p in net.collect_params().values())
    assert pending >= 2
    net.hybridize()
    x = mx.np.ones((3, 7))
    net(x)
    (settled,) = record.spans("block.settle_shapes")
    assert settled["cat"] == "setup" and settled["parent"] is None
    assert settled["args"] == {"block": net.name, "pending": pending}
    # the eager forward's small programs compiled inside it
    inside = [s for s in record.spans("xla.compile")
              if s["parent"] == settled["id"]]
    assert inside and all(settled["begin_ns"] <= s["begin_ns"] <= s["end_ns"]
                          <= settled["end_ns"] for s in inside)
    net(x)
    net.infer_shape(x)
    assert len(record.spans("block.settle_shapes")) == 1    # nothing pending


def test_the_first_prepare_builds_once(record):
    fused, args = _toy_fused_step()
    for _ in range(3):
        fused(*args, batch_size=4)
    (built,) = record.spans("fused_step.build")
    first_prepare = record.spans("fused_step.prepare")[0]
    assert built["parent"] == first_prepare["id"] and built["step"] == 1
    assert built["cat"] == "setup"
    assert first_prepare["begin_ns"] <= built["begin_ns"] <= built["end_ns"] \
        <= first_prepare["end_ns"]
    # Dense(1): weight and bias, one momentum each
    assert built["args"] == {"params": 2, "states": 2}


def test_block_trace_splits_a_steps_trace_by_block(record):
    """The `record` fixture takes the floor away: every block's trace shows."""
    fused, args = _toy_fused_step()
    fused(*args, batch_size=4)
    traces = record.spans("block.trace")
    assert [(s["args"]["cls"], s["cat"]) for s in traces] == \
        [("Dense", "compile"), ("WithLoss", "compile")]     # recorded at exit
    dense, with_loss = traces
    assert dense["parent"] == with_loss["id"] and dense["step"] == 1
    assert dense["args"]["block"] == fused._block.net.name
    assert with_loss["begin_ns"] <= dense["begin_ns"] <= dense["end_ns"] \
        <= with_loss["end_ns"]
    # the whole of it under the step's outermost trace, inside the launch
    (launch,) = record.spans("fused_step.launch")
    (step_trace,) = [s for s in record.spans("xla.trace")
                     if "fused" in s["args"]["fun_name"]]
    assert with_loss["parent"] == launch["id"] == step_trace["parent"]
    assert step_trace["begin_ns"] <= with_loss["begin_ns"] \
        and with_loss["end_ns"] <= step_trace["end_ns"]
    # a second step traces nothing, and an eager call is no trace
    fused(*args, batch_size=4)
    fused._block(*args)
    assert len(record.spans("block.trace")) == 2


def test_block_trace_under_a_hybridized_blocks_own_trace(record):
    net = _Deferred()
    net.initialize()
    net(mx.np.ones((3, 7)))                 # eager: shapes settle, no trace
    assert record.spans("block.trace") == []
    net.hybridize()
    net(mx.np.ones((3, 7)))
    traces = record.spans("block.trace")
    assert [s["args"]["cls"] for s in traces] == ["Dense", "Dense", "_Deferred"]
    assert {s["parent"] for s in traces[:2]} == {traces[2]["id"]}


def test_block_trace_records_nothing_under_the_floor(record, monkeypatch):
    monkeypatch.setattr(_WATCHDOG, "_TRACE_SPAN_FLOOR_S", float("inf"))
    fused, args = _toy_fused_step()
    fused(*args, batch_size=4)
    assert record.spans("block.trace") == []
    # the stack is as it was: the step's spans kept their parents
    (step,) = record.spans("fused_step.step")
    assert step["parent"] is None
    assert {s["parent"] for s in record.spans("fused_step.launch")} == {step["id"]}
    with telemetry.span("after", cat="unit") as after:
        pass
    assert after.parent is None


def test_a_span_under_its_floor_is_cancelled_and_one_over_it_is_kept(record):
    import time
    with telemetry.span("outer", cat="unit") as outer:
        with telemetry.span("short", cat="unit", floor_s=10.0) as short:
            with telemetry.span("inside", cat="unit"):
                pass
        with telemetry.span("long", cat="unit", floor_s=0.001):
            time.sleep(0.005)
    names = [s["name"] for s in record.spans()]
    assert names == ["inside", "long", "outer"]
    assert short.end_ns >= short.begin_ns           # the reading is still there
    (long_,) = record.spans("long")
    assert long_["parent"] == outer.id and "floor_s" not in long_["args"]


def test_setup_spans_outlive_the_ring(record):
    """10,000 spans after set-up: the ring wraps twice, set-up stays readable
    and `dropped` counts only what is gone."""
    fused, args = _toy_fused_step()
    for _ in range(2):
        fused(*args, batch_size=4)
    before = record.spans()
    assert before.dropped == 0 and before.setup_dropped == 0
    first_end = record.spans("fused_step.step")[0]["end_ns"]
    setup = [s for s in before if s["begin_ns"] < first_end]
    assert {"fused_step.build", "fused_step.prepare", "fused_step.launch",
            "xla.compile", "block.trace"} <= {s["name"] for s in setup}
    events_before = len(record.events())
    capacity = record.snapshot()["capacity"]
    for k in range(10_000):
        with telemetry.span("window", cat="unit", k=k):
            pass
    after = record.spans()
    assert after[:len(setup)] == setup              # ahead of the ring's
    assert [s["args"]["k"] for s in after[len(setup):]] == \
        list(range(10_000 - capacity, 10_000))
    # every event the ring lost, less the set-up spans kept beside it
    assert after.dropped == events_before + 10_000 - capacity - len(setup)
    assert after.setup_dropped == 0
    # the dump is the ring's, as it was
    snap = record.snapshot()
    assert snap["dropped"] == events_before + 10_000 - capacity
    assert len(snap["events"]) == capacity


def test_the_setup_store_is_bounded_and_says_when_it_is_full(monkeypatch):
    from mxnet_tpu.observe import FlightRecorder, flightrec
    monkeypatch.setattr(flightrec, "_SETUP_SPANS", 5)
    rec = FlightRecorder(capacity=4, enabled=True)
    for i in range(8):      # no step yet: all of it is set-up
        rec.record_span("unit", f"s{i}", begin_ns=i, end_ns=i + 1, id=i,
                        parent=None, step=None)
    got = rec.spans()
    assert [s["name"] for s in got] == ["s0", "s1", "s2", "s3", "s4",
                                        "s5", "s6", "s7"]
    assert got.setup_dropped == 3 and got.dropped == 0
    for i in (8, 9):
        rec.record_span("unit", f"s{i}", begin_ns=i, end_ns=i + 1, id=i,
                        parent=None, step=None)
    got = rec.spans()       # s4 is in the store; s5, the ring's alone, went
    assert [s["name"] for s in got] == ["s0", "s1", "s2", "s3", "s4",
                                        "s6", "s7", "s8", "s9"]
    assert got.setup_dropped == 5 and got.dropped == 1
    # a span that began before the first step ended is set-up's, whenever it ends
    rec = FlightRecorder(capacity=4, enabled=True)
    rec.record_span("step_phase", "fused_step.step", begin_ns=10, end_ns=20,
                    id=1, parent=None, step=1)
    rec.record_span("unit", "epoch", begin_ns=5, end_ns=90, id=2, parent=None,
                    step=None)
    for i in range(6):
        rec.record_span("unit", f"w{i}", begin_ns=30 + i, end_ns=31 + i,
                        id=10 + i, parent=None, step=None)
    got = rec.spans()
    assert [s["name"] for s in got] == ["fused_step.step", "epoch",
                                        "w2", "w3", "w4", "w5"]
    assert got.dropped == 2 and got.setup_dropped == 0
    rec.reset(capacity=4, enabled=True)
    assert rec.spans() == [] and rec.spans().setup_dropped == 0
