"""The readers of set-up's span record (`chipbench/setup_record.py`) and of the
kernel-path counters, on the CPU: each resolves to its entry, reads a
hand-made record, returns nothing on a record from before these spans, and
reads the record that a toy run left from the process's start.  The set-up
readers list every cell; each kernel-path counter lists the cell of its kernels
(`manifest_checks.py`)."""
import json
import subprocess
import sys

import pytest

import manifest_checks as checks
from chipbench import run, setup_record, trace

MANIFEST = run.load_json(run.ROOT, "BENCHMARK.json")
SETUP = checks.SETUP
PATHS = tuple(checks.KERNEL_PATHS)
MS = 1_000_000   # ns
reader = checks.reader


def span(name, begin_ms, end_ms, id, parent=None, step=None, **args):
    return {"name": name, "cat": "unit", "begin_ns": int(begin_ms * MS),
            "end_ns": int(end_ms * MS), "id": id, "parent": parent, "step": step,
            "args": args}


def made_record(imported=True):
    """A process that starts at 1,000 ms: 400 ms before the package, 300 of
    import with 100 of runtime start inside, 150 uncovered, 100 of shape
    settling that compiles one program, 50 uncovered, then a first step of
    600 that builds (40), traces (300, by block as below), lowers (100) and
    loads (120), 40 of it covered by nothing but the step; a second step that
    compiles again; a window of three."""
    runtime = [
        span("runtime.backend_start", 1450, 1550, 2, parent=1, platform="tpu", devices=1,
             already_up=True),
        span("runtime.import", 1400, 1700, 1),
        span("process.before_import", 1000, 1400, 3),
    ] if imported else []
    spans = runtime + [
        span("xla.lower", 1860, 1870, 11, parent=10, fun_name="jit(dot)"),
        span("xla.compile", 1870, 1900, 12, parent=10, fun_name="jit(dot)", cache_hit=False),
        span("block.settle_shapes", 1850, 1950, 10, block="net", pending=2),
        span("fused_step.build", 2000, 2040, 22, parent=21, step=1, params=4, states=8),
        span("fused_step.prepare", 2000, 2050, 21, parent=20, step=1),
        # the step's trace, 2060..2360: Net 300 holds Layer 100, Layer 120 (which
        # holds Attention 90) and Head 50; a Layer under 10 ms left no span
        span("block.trace", 2070, 2170, 32, parent=31, step=1, block="l0", cls="Layer"),
        span("block.trace", 2185, 2275, 34, parent=33, step=1, block="a1", cls="Attention"),
        span("block.trace", 2180, 2300, 33, parent=31, step=1, block="l1", cls="Layer"),
        span("block.trace", 2305, 2355, 35, parent=31, step=1, block="head", cls="Head"),
        span("block.trace", 2060, 2360, 31, parent=30, step=1, block="net", cls="Net"),
        span("xla.trace", 2055, 2365, 36, parent=30, step=1, fun_name="fused"),
        span("xla.lower", 2365, 2465, 37, parent=30, step=1, fun_name="jit(fused)"),
        span("xla.compile", 2465, 2585, 38, parent=30, step=1, fun_name="jit(fused)",
             cache_hit=True),
        span("fused_step.launch", 2050, 2590, 30, parent=20, step=1, compiled=True),
        span("fused_step.step", 2000, 2600, 20, step=1),
        span("xla.trace", 2700, 2750, 41, parent=40, step=2, fun_name="fused"),
        span("xla.compile", 2750, 2790, 42, parent=40, step=2, fun_name="jit(fused)",
             cache_hit=False),
        span("fused_step.step", 2690, 2800, 40, step=2),
    ]
    for k in range(3):
        spans.append(span("fused_step.step", 3000 + 10 * k, 3008 + 10 * k, 50 + k, step=3 + k))
    spans.append(span("xla.compile", 4000, 4050, 90, fun_name="jit(fused)", cache_hit=False))
    return spans


VALUES = {
    "setup_import_s": 0.7,                  # 1000..1700, the runtime's start inside it
    "setup_trace_s": 0.310 + 0.050,
    "setup_lower_s": 0.010 + 0.100,
    "setup_backend_s": 0.030 + 0.120 + 0.040,
    "setup_cache_misses": 2,                # not the hit, not the one after the window
    "setup_settle_s": 0.1,
    # Layer: 100 + (120 - 90); Net: 300 - 100 - 120 - 50; Attention 90; Head 50
    "setup_trace_self_max_s": 0.130,
    # 1000..2600 less import 700, settling 100 and the step 600
    "setup_unaccounted_s": 0.2,
}


def dispatches(n=3):
    return [("chipbench.dispatch", 0.1 + 0.01 * k, 0.108 + 0.01 * k) for k in range(n)]


def observed(monkeypatch, spans, dropped=0, setup_dropped=0):
    """`observe.spans()` returns `spans` as a recorder with a set-up store
    would, or as the parent's (`setup_dropped` None: no such attribute)."""
    from mxnet_tpu import observe
    from mxnet_tpu.observe import SpanList
    out = SpanList(dropped)
    out.extend(spans)
    if setup_dropped is None:
        del out.setup_dropped
    else:
        out.setup_dropped = setup_dropped
    monkeypatch.setattr(observe, "spans", lambda name=None: out)
    return trace.Trace([[("fusion f32[8]", 0.0, 1.0)]], dispatches())


@pytest.mark.parametrize("name", SETUP + PATHS)
def test_every_entry_resolves_to_a_reader_in_its_layer(name):
    if name in SETUP:
        checks.setup_entry(MANIFEST, name)
    else:
        checks.kernel_path_entry(MANIFEST, name)


def test_the_new_entries_are_appended_and_nothing_else_moved():
    checks.order_kept(MANIFEST)


@pytest.mark.parametrize("name", SETUP)
def test_each_setup_reader_on_a_hand_made_record(monkeypatch, name):
    tr = observed(monkeypatch, made_record())
    assert reader(name).read(tr, tr.spans, {}) == pytest.approx(VALUES[name])
    # a long window wrapped the ring, and set-up's store is whole: it still reads
    tr = observed(monkeypatch, made_record(), dropped=7000)
    assert reader(name).read(tr, tr.spans, {}) == pytest.approx(VALUES[name])
    # the store ran full: part of set-up is not on record
    tr = observed(monkeypatch, made_record(), setup_dropped=1)
    assert reader(name).read(tr, tr.spans, {}) is None


@pytest.mark.parametrize("name", SETUP)
def test_each_setup_reader_is_silent_on_a_record_from_before_these_spans(monkeypatch, name):
    """The parent commit under these files: `xla.*` spans, no import, no store."""
    tr = observed(monkeypatch, made_record(imported=False), setup_dropped=None)
    assert reader(name).read(tr, tr.spans, {}) is None
    tr = observed(monkeypatch, made_record(), dropped=1, setup_dropped=None)
    assert reader(name).read(tr, tr.spans, {}) is None      # a ring that lost events
    tr = observed(monkeypatch, made_record())
    none = trace.Trace(tr.ops, [])                          # no dispatch span
    assert reader(name).read(none, none.spans, {}) is None
    many = trace.Trace(tr.ops, dispatches(9))               # more than steps on record
    assert reader(name).read(many, many.spans, {}) is None
    from mxnet_tpu import observe
    monkeypatch.delattr(observe, "spans")                   # no span record at all
    assert reader(name).read(tr, tr.spans, {}) is None


def test_what_a_record_cannot_say_reads_none(monkeypatch):
    whole = made_record()
    no_start = [s for s in whole if s["name"] != "process.before_import"]   # no /proc
    tr = observed(monkeypatch, no_start)
    assert reader("setup_unaccounted_s").read(tr, tr.spans, {}) is None
    assert reader("setup_import_s").read(tr, tr.spans, {}) == pytest.approx(0.3)
    no_blocks = [s for s in whole if s["name"] != "block.trace"]   # none took 10 ms
    tr = observed(monkeypatch, no_blocks)
    assert reader("setup_trace_self_max_s").read(tr, tr.spans, {}) is None
    assert reader("setup_trace_s").read(tr, tr.spans, {}) == pytest.approx(0.36)


def test_self_time_by_class_sums_to_the_outermost_spans():
    by_class = setup_record.trace_self_s(made_record())
    assert by_class == pytest.approx({"Net": 0.030, "Layer": 0.130, "Attention": 0.090,
                                      "Head": 0.050})
    assert sum(by_class.values()) == pytest.approx(0.300)


@pytest.mark.parametrize("name,counter,kernel,other", [
    ("kda_kernel_path_pct.tok", "mxtpu_linear_attention_lowerings", "pallas_chunk",
     "chunked_scan"),
    ("gmm_kernel_path_pct.tok", "mxtpu_grouped_matmul_lowerings", "pallas", "ragged_dot"),
    ("mhc_kernel_path_pct.tok", "mxtpu_hyperconnection_lowerings", "pallas", "xla")])
def test_kernel_path_share_reads_the_lowering_counter(monkeypatch, name, counter, kernel, other):
    from mxnet_tpu import telemetry
    registry = telemetry.MetricsRegistry()
    monkeypatch.setattr(telemetry, "default_registry", lambda: registry)
    tr = trace.Trace([[("fusion f32[8]", 0.0, 1.0)]], dispatches())
    assert reader(name).read(tr, tr.spans, {}) is None          # no such counter
    paths = registry.counter(counter, "made", labelnames=("path",))
    registry.counter("mxtpu_other_lowerings", "made", labelnames=("path",)) \
        .labels(path=other).inc(5)
    paths.labels(path=kernel).inc(0)
    assert reader(name).read(tr, tr.spans, {}) is None          # nothing traced
    paths.labels(path=kernel).inc(4)
    assert reader(name).read(tr, tr.spans, {}) == 100.0
    paths.labels(path=other).inc(1)                             # a silent fall-back
    assert reader(name).read(tr, tr.spans, {}) == pytest.approx(80.0)


TOY_RUN = """
import json, os, sys
from chipbench import run, trace
run.PLATFORM = "cpu"
run.peaks_of = lambda kind: {"bf16_flops_per_s": 1e12}
name = "bert_base.phase1_t128"
manifest, chips, cell, cfg, bert = run.load_cell(name)
cell = dict(cell, batch=4, seq_len=16, valid_lengths=[8, 16], ring=2, trace_steps=4,
            loss_band={"first": [4.0, 7.0]})
cfg = dict(cfg, vocab_size=97, hidden_size=32, num_hidden_layers=2, num_attention_heads=2,
           intermediate_size=64, max_position_embeddings=32)
out = run.measure(name, manifest, chips, cell, cfg, bert, seed=2 ** 31 + 11, seconds=0.2,
                  traced=False)
from mxnet_tpu import observe
made = trace.Trace([[("fusion f32[8]", 0.0, 1.0)]],
                   [("chipbench.dispatch", k, k + 0.5) for k in range(out["attempted"])])
read = {}
for m in sys.argv[1:]:
    reader = run.load_py(os.path.join(run.HERE, "layer_metrics", m + ".py"))
    read[m] = reader.read(made, made.spans, cell)
spans = observe.spans()
first = observe.spans("fused_step.step")[0]
print(json.dumps({"correct": out["correct"], "read": read, "setup_s":
                  out["metrics"]["setup_s"]["value"], "dropped": spans.dropped,
                  "setup_dropped": spans.setup_dropped,
                  "names": sorted({s["name"] for s in spans}),
                  "first_step_end_s": first["end_ns"] / 1e9,
                  "start_s": next(s["begin_ns"] for s in spans
                                  if s["name"] == "process.before_import") / 1e9}))
"""


@pytest.fixture(scope="module")
def toy_run():
    """A toy rehearsal of a whole untraced run in a process of its own, so the
    record runs from that process's start; then every new reader against it."""
    names = SETUP + PATHS + ("setup_compile_s", "setup_programs")
    done = subprocess.run([sys.executable, "-c", TOY_RUN, *names], cwd=run.ROOT, timeout=600,
                          capture_output=True, text=True)
    assert done.returncode == 0, done.stderr[-3000:]
    return json.loads(done.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("name", SETUP)
def test_each_setup_reader_reads_a_toy_runs_record(toy_run, name):
    value = toy_run["read"][name]
    assert toy_run["correct"] and toy_run["dropped"] == toy_run["setup_dropped"] == 0
    if name == "setup_trace_self_max_s":
        assert value is None or value >= 0.01       # a toy's blocks may all be under 10 ms
        return
    assert value is not None and value >= 0
    if name == "setup_import_s":
        assert 0 < value < toy_run["setup_s"]
    if name == "setup_cache_misses":                # a CPU run has no persistent cache
        assert value == toy_run["read"]["setup_programs"]
    if name == "setup_unaccounted_s":
        assert value < toy_run["first_step_end_s"] - toy_run["start_s"]


def test_the_stage_split_covers_what_setup_compile_s_reads(toy_run):
    read = toy_run["read"]
    parts = read["setup_trace_s"] + read["setup_lower_s"] + read["setup_backend_s"]
    assert parts >= read["setup_compile_s"] * (1 - 1e-9) > 0
    # the cell's builder settles its shapes by an eager forward of its own
    assert read["setup_settle_s"] == 0 and "block.settle_shapes" not in toy_run["names"]
    assert {"process.before_import", "runtime.import", "runtime.backend_start",
            "fused_step.build"} <= set(toy_run["names"])
    assert all(read[name] is None for name in PATHS)           # BERT runs none of them
