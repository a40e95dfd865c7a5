"""CPU-only checks of the benchmark harness under chipbench/: the manifest
resolves to files, the trace arithmetic on a hand-made event list, the lag-2
window on a fake clock, the valid-token count, a toy-size rehearsal of a
whole run, and a rehearsal of an addition to the benchmark by new files and
appends alone, on which every cell file's manifest checks run too.  Nothing
here needs the chip or describes a topology."""
import ast
import copy
import glob
import importlib
import json
import os
import shutil

import pytest

import manifest_checks as checks
from chipbench import run, trace

ROOT = run.ROOT
HERE = os.path.dirname(os.path.abspath(__file__))
MANIFEST = run.load_json(ROOT, "BENCHMARK.json")
TINY_BERT = dict(vocab_size=97, hidden_size=32, num_hidden_layers=2, num_attention_heads=2,
                 intermediate_size=64, max_position_embeddings=32)
TINY_CELL = dict(batch=4, seq_len=16, valid_lengths=[8, 16], ring=2, trace_steps=4,
                 loss_band={"first": [4.0, 7.0]})


def test_manifest_names_units_and_arrows():
    checks.names_units_and_arrows(MANIFEST)


def test_every_per_layer_entry_lists_its_cells():
    checks.per_layer_entries_list_their_cells(MANIFEST)


@pytest.mark.parametrize("cell", checks.cells(MANIFEST))
def test_cell_resolves_to_its_files(cell):
    checks.cell_files(MANIFEST, cell)


@pytest.fixture(scope="module")
def fixture():
    return run.load_json(HERE, "trace_fixture.json")


@pytest.fixture(scope="module")
def made(fixture):
    return trace.Trace([[tuple(o) for o in chip] for chip in fixture["ops"]],
                       [tuple(s) for s in fixture["spans"]])


def test_trace_busy_union_and_idle_share(fixture, made):
    want = fixture["expect"]
    assert made.window_s == pytest.approx(want["window_s"])
    assert trace.total(made.busy(0)) == pytest.approx(want["busy_s_chip0"])
    assert made.busy_s == pytest.approx(want["busy_s_mean"])
    assert made.idle_pct(0) == pytest.approx(want["idle_pct"])
    assert trace.union([(0, 2), (1, 3), (5, 6)]) == [(0, 3), (5, 6)]
    assert trace.subtract([(0, 10)], [(2, 3), (5, 12)]) == [(0, 2), (3, 5)]


def test_trace_exposed_collective_and_kernel_share(fixture, made):
    want = fixture["expect"]
    assert made.exposed_collective_pct(0) == pytest.approx(want["exposed_collective_pct"])
    assert made.span_median_ms("chipbench.dispatch") == pytest.approx(want["dispatch_median_ms"])


def test_trace_gap_attribution_and_breakdown(fixture, made):
    want = fixture["expect"]
    got = made.gaps(0)
    assert [g[0] for g in got] == [g[0] for g in want["gaps"]]
    assert [g[1] for g in got] == pytest.approx([g[1] for g in want["gaps"]])
    top = made.breakdown()["device_ops"][0]
    assert top[0] == want["top_op"][0] and top[1] == pytest.approx(want["top_op"][1])
    assert len(made.breakdown()["idle_gaps"]) == 2


def test_labels_from_the_hlo_text_the_trace_gives():
    bn = ('%_bn_reduce_call.105 = (f32[1,64]{1,0:T(1,128)S(1)}, f32[1,64]{1,0:T(1,128)S(1)}) '
          'custom-call(f32[3211264,64]{1,0:T(8,128)} %bitcast.236, f32[3211264,64]{1,0} %custom-call.2), '
          'custom_call_target="tpu_custom_call", operand_layout_constraints={f32[3211264,64]{1,0}}')
    assert trace.label_of(bn) == "tpu_custom_call:_bn_reduce_call (f32[1,64], f32[1,64])"
    copy = "%copy.913 = f32[256,112,112,64]{3,2,1,0:T(8,128)} copy(f32[256,112,112,64]{0,3,2,1:T(8,128)} %custom-call.19)"
    assert trace.label_of(copy) == "copy f32[256,112,112,64]"
    assert not trace.is_custom_call(trace.label_of(copy)) and trace.is_custom_call(trace.label_of(bn))
    ar = "%ar.3 = (f32[64]{0}, f32[64]{0}) all-reduce-start(f32[64]{0} %p), replica_groups={{0,1,2,3}}"
    assert trace.is_collective(trace.label_of(ar)) and not trace.is_collective(trace.label_of(bn))
    assert trace.label_of("jit_fused(123)") == "jit_fused(123)"


def test_layer_metric_readers_return_nothing_where_nothing_is(made):
    reader = checks.reader
    plain = trace.Trace([[("fusion f32[8]", 0.0, 1.0)]], [])
    assert reader("collective_exposed_pct.img").read(plain, plain.spans, {}) is None
    assert reader("dispatch_ms.img").read(plain, plain.spans, {}) is None
    assert reader("collective_exposed_pct.img").read(made, made.spans, {}) == pytest.approx(5.0)
    assert reader("device_idle_pct.tok").read(made, made.spans, {}) == pytest.approx(15.0)


def test_lag2_window_on_a_fake_clock():
    """Each step takes 10 ticks on a device that runs them in order; the host
    dispatches in 1 tick.  Completion stamps must be the device's, 10 apart,
    and never more than LAG + 1 steps may be outstanding."""
    now, device_free, outstanding, worst = [0.0], [0.0], [0], [0]

    def clock():
        return now[0]

    def dispatch(k):
        now[0] += 1.0
        device_free[0] = max(device_free[0], now[0]) + 10.0
        outstanding[0] += 1
        worst[0] = max(worst[0], outstanding[0])
        return device_free[0]

    def wait(done_at):
        now[0] = max(now[0], done_at)
        outstanding[0] -= 1

    begin, stamps = run.run_window(dispatch, wait, seconds=100.0, clock=clock)
    assert begin == 0.0 and stamps[0] == 11.0 and worst[0] == run.LAG + 1
    assert [b - a for a, b in zip(stamps, stamps[1:])] == [10.0] * (len(stamps) - 1)
    assert run.interval_percentile_ms(stamps, 95) == pytest.approx(10e3)
    assert stamps[-1] >= 100.0 and outstanding[0] == 0
    _, stamps = run.run_window(dispatch, wait, seconds=0, clock=clock, steps=7)
    assert len(stamps) == 7


def test_interval_percentile_interpolates():
    assert run.interval_percentile_ms([0, 1, 3, 6, 10], 50) == pytest.approx(2500.0)
    squares = [k * k * 1e-3 for k in range(102)]       # intervals 1, 3, 5, ... 201 ms
    assert run.interval_percentile_ms(squares, 95) == pytest.approx(191.0)
    assert run.interval_percentile_ms([0.0, 0.007], 95) == pytest.approx(7.0)


def test_what_makes_a_run_incorrect():
    band = {"first": [6.4, 8.4]}
    assert run.is_correct(0, 0, [7.8, 7.5, 6.2], band)
    assert not run.is_correct(1, 0, [7.8, 7.5, 6.2], band)            # a compile in the window
    assert not run.is_correct(0, 2, [7.8, 7.5, 6.2], band)            # non-finite losses in it
    assert not run.is_correct(0, 0, [7.8, 7.9, 7.85], band)           # warm-up did not fall
    assert not run.is_correct(0, 0, [9.0, 7.0], band)                 # starts outside the band
    assert not run.is_correct(0, 0, [7.8, float("nan"), 6.0], band)   # warm-up not finite


def test_the_numbers_compared_stand_beside_their_limits(monkeypatch, capsys):
    """The result line's last key, and the last lines of standard error, give
    every number `correct` compares with its limit."""
    band = {"first": [6.4, 8.4]}
    got = run.compared(1, 2, [7.8, float("nan"), 6.2], band)
    assert {k: v["value"] for k, v in got.items() if k != "warmup_loss_change"} == {
        "compiles_in_window": 1, "failed_steps": 2, "nonfinite_warmup_losses": 1,
        "first_warmup_loss": 7.8}
    assert got["warmup_loss_change"]["value"] == pytest.approx(-1.6)
    assert got["first_warmup_loss"]["limit"] == [6.4, 8.4]
    result = {"correct": False, "attempted": 3, "failed": 2, "metrics": {}, "device": {},
              "compared": got}
    monkeypatch.setattr(run, "load_cell", lambda name: (MANIFEST, 1, {}, {}, None))
    monkeypatch.setattr(run, "measure", lambda *args: result)
    assert run.main(["--workload", "any", "--seed", str(2 ** 31 + 5)]) == 0
    out, err = capsys.readouterr()
    assert list(json.loads(out.strip().splitlines()[-1]))[-1] == "compared"
    assert err.strip().splitlines()[-len(got):] == [
        "compared compiles_in_window 1 limit == 0", "compared failed_steps 2 limit == 0",
        "compared nonfinite_warmup_losses 1 limit == 0",
        "compared first_warmup_loss 7.8 limit [6.4, 8.4]",
        f"compared warmup_loss_change {got['warmup_loss_change']['value']!r} limit < 0"]


def test_valid_token_count_matches_the_mask():
    _m, _c, _cell, cfg, bert = run.load_cell("bert_base.phase1_t128")
    cfg, cell = dict(cfg, **TINY_BERT), TINY_CELL
    totals = set()
    for seed in (3, 2 ** 31 + 11):
        ring = bert.make_ring(cfg, cell, 1, seed, None)
        for (tokens, _seg, _labels, mask), work in ring:
            assert tokens.shape == (4, 16) and int(mask.asnumpy().sum()) == work
        totals.add(sum(work for _args, work in ring))
    assert len(totals) == 1   # every seed draws the same lengths, in another order


def test_runner_refuses_a_platform_that_is_not_tpu():
    with pytest.raises(SystemExit, match="needs 1 tpu"):
        run.measure("bert_base.phase1_t128", *run.load_cell("bert_base.phase1_t128"),
                    seed=0, seconds=0.1, traced=False)
    with pytest.raises(SystemExit, match="no device kind"):
        run.peaks_of("cpu")


def test_toy_rehearsal_of_a_whole_run(monkeypatch, capsys):
    """The whole of `measure` at a toy size on the CPU, through overrides made
    here and not through an option of the runner."""
    monkeypatch.setattr(run, "PLATFORM", "cpu")
    monkeypatch.setattr(run, "peaks_of", lambda kind: {"bf16_flops_per_s": 1e12})
    name = "bert_base.phase1_t128"
    manifest, chips, cell, cfg, bert = run.load_cell(name)
    out = run.measure(name, manifest, chips, dict(cell, **TINY_CELL), dict(cfg, **TINY_BERT),
                      bert, seed=2 ** 31 + 5, seconds=0.3, traced=False)
    assert out["correct"] and out["failed"] == 0 and out["attempted"] >= 3
    assert set(out["metrics"]) == checks.end_to_end_of(manifest, name)
    assert all(v["value"] > 0 for v in out["metrics"].values())
    assert out["device"]["platform"] == "cpu"
    earlier = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert earlier["compiles_in_window"] == 0 and earlier["mfu_bf16"] > 0
    assert list(out)[-1] == "compared"
    assert out["compared"] == run.compared(0, 0, earlier["warmup_losses"], TINY_CELL["loss_band"])


NEW_CELL = "toy_lm.short"
NEW_METRIC = "toy_idle_pct.tok"
SHARED_METRIC = "toy_shared_idle_pct.tok"
XING_CELL = "xing4_29b_a4b.sft_t8192_ep8share"
NEW_READER = '''"""A made-up per-layer metric: device 0's idle share of the window."""


def read(trace, spans, cell):
    return trace.idle_pct(0)
'''
# the test files of the cells, each with its MANIFEST_CHECKS: found by name, no list to edit
CELL_FILES = sorted(glob.glob(os.path.join(HERE, "test_*_cell.py")))
MANIFEST_LISTS = {"configs", "workloads", "end_to_end", "per_layer"}


def write_json(value, *parts):
    with open(os.path.join(*parts), "w") as f:
        json.dump(value, f, indent=1)


def manifest_checks_of(path):
    """The MANIFEST_CHECKS of a cell's test file: what must hold of its cell in
    a manifest, each a function of one."""
    found = getattr(importlib.import_module(os.path.basename(path)[:-len(".py")]),
                    "MANIFEST_CHECKS", ())
    assert found, f"{os.path.basename(path)} has no MANIFEST_CHECKS: give it its manifest " \
        "assertions as functions of one manifest"
    return found


def checkout_with_an_addition(root, addition):
    """A checkout at `root` with the benchmark's files as they are and, for an
    `addition`, one configuration, one cell and one per-layer metric added by
    new files and appends alone; the toy BERT's builder serves as the new
    configuration's.  For "no_workloads_list" the metric's entry has no
    `workloads` key.  "decoder_cell_shared_metric" appends as a decoder
    configuration does: the cell also to `dispatch_ms.tok` and
    `device_idle_pct.tok`, and a second new metric shared with the Xing cell.
    Returns the checkout's manifest."""
    here = os.path.join(root, "chipbench")
    shutil.copytree(run.HERE, here, ignore=shutil.ignore_patterns("__pycache__"))
    manifest = copy.deepcopy(MANIFEST)
    if addition != "nothing":
        bert = run.load_json(run.HERE, "configs", "bert_base.json")
        write_json(dict(bert, name="toy_lm", **TINY_BERT), here, "configs", "toy_lm.json")
        shutil.copy(os.path.join(run.HERE, "configs", "bert_base.py"),
                    os.path.join(here, "configs", "toy_lm.py"))
        cell = run.load_json(run.HERE, "workloads", "bert_base.phase1_t128.json")
        write_json(dict(cell, name=NEW_CELL, config="toy_lm", **TINY_CELL),
                   here, "workloads", NEW_CELL + ".json")
        for name in (NEW_METRIC, SHARED_METRIC):
            with open(os.path.join(here, "layer_metrics", name + ".py"), "w") as f:
                f.write(NEW_READER)
        manifest["configs"].append({
            "name": "toy_lm", "source": "a made-up configuration of this test",
            "file": "chipbench/configs/toy_lm.json", "reduced": [], "why": "a rehearsal"})
        manifest["workloads"].append({"name": NEW_CELL, "config": "toy_lm", "traffic": "short",
                                      "chips": 1, "why": "a rehearsal"})
        listed_under = {"tokens_per_s", *checks.SETUP}
        if addition == "decoder_cell_shared_metric":
            listed_under |= {"dispatch_ms.tok", "device_idle_pct.tok"}
        for m in manifest["end_to_end"] + manifest["per_layer"]:
            if m["name"] in listed_under:
                m["workloads"].append(NEW_CELL)
        metric = {"name": NEW_METRIC, "unit": "%", "better": "lower", "source": "device_trace",
                  "layer": "device (v5e)", "moves": "tokens_per_s", "workloads": [NEW_CELL]}
        if addition == "no_workloads_list":
            del metric["workloads"]
        manifest["per_layer"].append(metric)
        if addition == "decoder_cell_shared_metric":
            manifest["per_layer"].append(dict(metric, name=SHARED_METRIC,
                                              workloads=[XING_CELL, NEW_CELL]))
    write_json(manifest, root, "BENCHMARK.json")
    return manifest


@pytest.mark.parametrize("addition", ["nothing", "cell_config_metric", "no_workloads_list",
                                      "decoder_cell_shared_metric"])
def test_an_addition_takes_new_files_and_appends(tmp_path, monkeypatch, addition):
    """Every manifest check, and every cell file's MANIFEST_CHECKS, holds of
    the benchmark plus a configuration, a cell and metrics that came by new
    files and appends to lists, no file that is there edited; a metric with no
    `workloads` list fails them."""
    manifest = checkout_with_an_addition(str(tmp_path), addition)
    monkeypatch.setattr(run, "ROOT", str(tmp_path))
    monkeypatch.setattr(run, "HERE", str(tmp_path / "chipbench"))
    if addition == "no_workloads_list":
        with pytest.raises(AssertionError, match=f"{NEW_METRIC} has no `workloads` list"):
            checks.every_check(manifest)
        return
    checks.every_check(manifest)
    for path in CELL_FILES:
        for check in manifest_checks_of(path):
            check(manifest)
    if addition == "nothing":
        return
    # the runner takes the new cell, with the end-to-end metrics it was appended to
    monkeypatch.setattr(run, "PLATFORM", "cpu")
    monkeypatch.setattr(run, "peaks_of", lambda kind: {"bf16_flops_per_s": 1e12})
    loaded = run.load_cell(NEW_CELL)
    out = run.measure(NEW_CELL, *loaded, seed=2 ** 31 + 13, seconds=0.3, traced=False)
    assert out["correct"] and set(out["metrics"]) == {"tokens_per_s", "step_ms_p95", "setup_s"}
    layer = {m["name"] for m in run.metrics_of(manifest, "per_layer", NEW_CELL)}
    assert layer >= set(checks.SETUP) | {NEW_METRIC}
    if addition == "decoder_cell_shared_metric":
        assert layer >= {"dispatch_ms.tok", "device_idle_pct.tok", SHARED_METRIC}
        assert SHARED_METRIC in {m["name"] for m in run.metrics_of(manifest, "per_layer",
                                                                   XING_CELL)}
    made = trace.Trace([[("fusion f32[8]", 0.0, 1.0), ("fusion f32[8]", 3.0, 4.0)]], [])
    assert checks.reader(NEW_METRIC).read(made, made.spans, loaded[2]) == pytest.approx(50.0)


def is_a_read(node):
    """Whether `node` itself reads one of the manifest's lists."""
    return (isinstance(node, ast.Subscript) and isinstance(node.slice, ast.Constant)
            and node.slice.value in MANIFEST_LISTS) or \
        (isinstance(node, ast.Attribute) and node.attr == "metrics_of")


def reads_the_manifest(node):
    return any(is_a_read(n) for n in ast.walk(node))


def freezes(node):
    """Whether `node` takes a position from the end, a length or an equality
    of what reads one of the manifest's lists."""
    if isinstance(node, ast.Subscript) and reads_the_manifest(node.value):
        at = node.slice.lower if isinstance(node.slice, ast.Slice) else node.slice
        return isinstance(at, ast.UnaryOp) and isinstance(at.op, ast.USub)
    if isinstance(node, ast.Call) and getattr(node.func, "id", None) == "len":
        return any(reads_the_manifest(a) for a in node.args)
    if isinstance(node, ast.Compare) and any(isinstance(op, (ast.Eq, ast.NotEq))
                                             for op in node.ops):
        return any(reads_the_manifest(side) for side in [node.left, *node.comparators])
    return False


@pytest.mark.parametrize("path", CELL_FILES, ids=os.path.basename)
def test_a_cell_file_asserts_on_the_manifest_in_its_checks_alone(path):
    """A cell's test file reads the manifest's lists only in the functions of
    its MANIFEST_CHECKS, which the rehearsal of an addition runs on appended
    entries, and takes no position, length or exact set of them anywhere:
    what must hold of its cell, never where things stand."""
    named = {f.__name__ for f in manifest_checks_of(path)}
    with open(path) as f:
        tree = ast.parse(f.read())
    for top in tree.body:
        inside = isinstance(top, ast.FunctionDef) and top.name in named
        for node in ast.walk(top):
            where = f"{os.path.basename(path)}:{getattr(node, 'lineno', top.lineno)}"
            assert inside or not is_a_read(node), \
                f"{where} reads the manifest outside MANIFEST_CHECKS: move it into one"
            assert not freezes(node), \
                f"{where} pins a position, a length or an exact set of a manifest list"
