"""The readers of the program's own span record, on the CPU: each new per-layer
metric resolves to a reader, reads a hand-made record, returns nothing where the
reading would be wrong, and reads the record a real (toy) run left."""
import json

import pytest

import manifest_checks as checks
from chipbench import program_record, run, trace

MANIFEST = run.load_json(run.ROOT, "BENCHMARK.json")
NEW = checks.PROGRAM
MS = 1_000_000   # ns
reader = checks.reader


def span(name, begin_ms, end_ms, id, parent=None, step=None, **args):
    return {"name": name, "cat": "unit", "begin_ns": int(begin_ms * MS),
            "end_ns": int(end_ms * MS), "id": id, "parent": parent, "step": step,
            "args": args}


def made_record():
    """Set-up with an initialize, two compiling launches and three programs,
    of which two overlap; then a window of three steps 10 ms apart."""
    spans = [
        span("xla.compile", 10, 20, 12, parent=11, fun_name="jit(a)", cache_hit=True),
        span("block.initialize", 0, 60, 11, block="net", params=2),
        span("xla.trace", 0, 40, 1, fun_name="a"),
        span("xla.compile", 30, 100, 2, fun_name="jit(a)", cache_hit=False),   # union 0..100
        span("xla.compile", 200, 250, 3, fun_name="jit(b)", cache_hit=True),   # + 50
        span("fused_step.launch", 300, 460, 5, parent=4, step=1, compiled=True),
        span("xla.compile", 310, 450, 6, parent=5, step=1, fun_name="jit(fused)", cache_hit=False),
        span("fused_step.step", 290, 470, 4, step=1),
        span("fused_step.launch", 500, 600, 8, parent=7, step=2, compiled=True),
        span("fused_step.step", 495, 605, 7, step=2),
        span("fused_step.launch", 700, 701, 10, parent=9, step=3, compiled=False),
        span("fused_step.step", 699, 702, 9, step=3),
    ]
    for k, (prepare, launch) in enumerate([(1.0, 4.0), (2.0, 5.0), (3.0, 9.0)]):
        t, sid = 1000 + 10 * k, 20 + 10 * k
        spans += [span("fused_step.prepare", t, t + prepare, sid + 1, parent=sid, step=4 + k),
                  span("fused_step.launch", t + prepare, t + prepare + launch, sid + 2,
                       parent=sid, step=4 + k, compiled=False),
                  span("fused_step.step", t, t + prepare + launch + 0.5, sid, step=4 + k)]
    # after the window: the runner lowers the step again for its facts
    spans += [span("fused_step.prepare", 2000, 2002, 90),
              span("xla.compile", 2002, 2050, 91, fun_name="jit(fused)", cache_hit=True)]
    return spans


def dispatches(n):
    """As many `chipbench.dispatch` spans as the window had steps, on a trace
    clock that starts near zero, and one span of another name."""
    return [("chipbench.dispatch", 0.1 + 0.01 * k, 0.108 + 0.01 * k) for k in range(n)] + \
           [("chipbench.wait", 0.0, 0.001)]


@pytest.mark.parametrize("name", NEW)
def test_every_new_metric_resolves_to_a_reader(name):
    checks.program_entry(MANIFEST, name)


def test_what_the_benchmark_had_is_still_there():
    """The first per-layer metrics keep their names and order, ahead of the new ones."""
    checks.order_kept(MANIFEST)


def test_record_of_a_hand_made_span_list():
    record = program_record.of(made_record(), 0, dispatches(3))
    assert [s["step"] for s in record.window] == [4, 5, 6]
    assert record.median_ms("fused_step.prepare") == pytest.approx(2.0)
    assert record.median_ms("fused_step.launch") == pytest.approx(5.0)
    assert record.median_ms("fused_step.step") == pytest.approx(7.5)
    assert record.median_ms("serve/batch") is None            # none on this record
    # set-up: 0..100 ms and 200..250 ms and the first step's 310..450 ms
    assert record.covered_before_s(program_record.XLA_STAGES) == \
        pytest.approx(0.100 + 0.050 + 0.140)
    assert record.covered_before_s(("block.initialize",)) == pytest.approx(0.060)
    assert record.count_before("xla.compile") == 4            # not the one after
    assert record.count_before("fused_step.launch", compiled=True) == 2
    assert record.count_before("fused_step.launch") == 3


def test_window_is_the_last_n_steps():
    record = program_record.of(made_record(), 0, dispatches(2))
    assert [s["step"] for s in record.window] == [5, 6]       # the LAST two
    assert record.median_ms("fused_step.prepare") == pytest.approx(2.5)


def test_a_ring_that_lost_events_silences_only_what_it_may_have_lost():
    """Oldest first: the set-up goes, then the window's first `prepare`, which
    was recorded before the step it belongs to."""
    whole = made_record()
    lost_setup = program_record.of(whole[4:], 4, dispatches(3))
    assert lost_setup.median_ms("fused_step.launch") == pytest.approx(5.0)
    assert lost_setup.covered_before_s(program_record.XLA_STAGES) is None
    assert lost_setup.count_before("xla.compile") is None
    first_prepare = next(k for k, s in enumerate(whole) if s["step"] == 4)
    cut = program_record.of(whole[first_prepare + 1:], first_prepare + 1, dispatches(3))
    assert cut.median_ms("fused_step.prepare") is None        # two for three steps
    assert cut.median_ms("fused_step.launch") == pytest.approx(5.0)
    assert program_record.of(whole[first_prepare + 3:], 1, dispatches(3)) is None


WINDOW = ("step_prepare_ms.img", "step_prepare_ms.tok", "step_launch_ms.img",
          "step_launch_ms.tok")


@pytest.mark.parametrize("name,value", [
    ("step_prepare_ms.img", 2.0), ("step_prepare_ms.tok", 2.0),
    ("step_launch_ms.img", 5.0), ("step_launch_ms.tok", 5.0),
    ("setup_compile_s", 0.29), ("setup_programs", 4), ("setup_step_programs", 2),
    ("setup_initialize_s", 0.06)])
def test_each_reader_on_a_hand_made_record(monkeypatch, name, value):
    from mxnet_tpu import observe
    from mxnet_tpu.observe import SpanList

    def spans_with(dropped):
        out = SpanList(dropped)
        out.extend(made_record())
        return out

    tr = trace.Trace([[("fusion f32[8]", 0.0, 1.0)]], dispatches(3))
    monkeypatch.setattr(observe, "spans", lambda name=None: spans_with(0))
    assert reader(name).read(tr, tr.spans, {}) == pytest.approx(value)
    # events lost from the ring: set-up may be among them; the window is whole
    monkeypatch.setattr(observe, "spans", lambda name=None: spans_with(1))
    assert reader(name).read(tr, tr.spans, {}) == \
        (pytest.approx(value) if name in WINDOW else None)
    # more dispatches in the trace than steps on record
    monkeypatch.setattr(observe, "spans", lambda name=None: spans_with(0))
    many = trace.Trace(tr.ops, [("chipbench.dispatch", k, k + 0.01) for k in range(7)])
    assert reader(name).read(many, many.spans, {}) is None
    # a trace with no dispatch span, the recorder switched off, and a program
    # from before the span record (the parent commit under these files)
    none = trace.Trace(tr.ops, [])
    assert reader(name).read(none, none.spans, {}) is None
    monkeypatch.setattr(observe, "spans", lambda name=None: SpanList(0))
    assert reader(name).read(tr, tr.spans, {}) is None
    monkeypatch.delattr(observe, "spans")
    assert reader(name).read(tr, tr.spans, {}) is None


TINY_BERT = dict(vocab_size=97, hidden_size=32, num_hidden_layers=2, num_attention_heads=2,
                 intermediate_size=64, max_position_embeddings=32)
TINY_CELL = dict(batch=4, seq_len=16, valid_lengths=[8, 16], ring=2, trace_steps=4,
                 loss_band={"first": [4.0, 7.0]})


@pytest.fixture(scope="module")
def rehearsed():
    """The harness's untraced toy rehearsal (a traced run cannot be rehearsed
    here: `trace.load` wants a TPU plane), then a hand-made `Trace` holding as
    many `chipbench.dispatch` spans as the window had steps."""
    from mxnet_tpu import observe
    observe.reset(enabled=True)
    patch = pytest.MonkeyPatch()
    patch.setattr(run, "PLATFORM", "cpu")
    patch.setattr(run, "peaks_of", lambda kind: {"bf16_flops_per_s": 1e12})
    try:
        name = "bert_base.phase1_t128"
        manifest, chips, cell, cfg, bert = run.load_cell(name)
        out = run.measure(name, manifest, chips, dict(cell, **TINY_CELL),
                          dict(cfg, **TINY_BERT), bert, seed=2 ** 31 + 7, seconds=0.3,
                          traced=False)
        steps = observe.spans("fused_step.step")[-out["attempted"]:]
        made = trace.Trace(
            [[("fusion f32[8]", 0.0, 1.0)]],
            [("chipbench.dispatch", s["begin_ns"] / 1e9 - 5e-5, s["end_ns"] / 1e9 + 5e-5)
             for s in steps])
        yield out, made, observe.spans()
    finally:
        patch.undo()
        observe.reset()


@pytest.mark.parametrize("name", NEW)
def test_every_reader_reads_the_record_a_toy_run_left(rehearsed, monkeypatch, name):
    from mxnet_tpu import observe
    out, made, spans = rehearsed
    monkeypatch.setattr(observe, "spans", lambda name=None: spans)
    value = reader(name).read(made, made.spans, {})
    assert out["correct"] and value is not None and value >= 0
    if name == "setup_step_programs":
        assert value == 1           # Adam in this toy: the step compiles once
    if name == "setup_programs":
        assert value >= 1 and value == int(value)
    json.dumps(value)


def test_the_toy_runs_record_is_whole_and_ordered(rehearsed):
    out, made, spans = rehearsed
    record = program_record.of(spans, spans.dropped, made.spans)
    assert spans.dropped == 0 and len(record.window) == out["attempted"]
    numbers = [s["step"] for s in record.window]
    assert numbers == list(range(numbers[0], numbers[0] + len(numbers)))
    # warm-up: 8 steps on the first batch and one on each of the ring's 2
    assert numbers[0] == 8 + 2 + 1
    # the parts of a step do not exceed the step
    parts = record.median_ms("fused_step.prepare") + record.median_ms("fused_step.launch")
    assert parts <= max((s["end_ns"] - s["begin_ns"]) / 1e6 for s in record.window)
    # no compile inside the window, on the record as in the runner's count
    begin, end = record.window[0]["begin_ns"], record.window[-1]["end_ns"]
    assert not [s for s in spans if s["name"] == "xla.compile"
                and begin <= s["begin_ns"] <= end]
