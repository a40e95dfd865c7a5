"""CPU-only checks of the benchmark harness under chipbench/: the manifest
resolves to files, the trace arithmetic on a hand-made event list, the lag-2
window on a fake clock, the valid-token count, and a toy-size rehearsal of a
whole run.  Nothing here needs the chip or describes a topology."""
import json
import os
import re

import pytest

from chipbench import run, trace

ROOT = run.ROOT
HERE = os.path.dirname(os.path.abspath(__file__))
MANIFEST = run.load_json(ROOT, "BENCHMARK.json")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
TINY_BERT = dict(vocab_size=97, hidden_size=32, num_hidden_layers=2, num_attention_heads=2,
                 intermediate_size=64, max_position_embeddings=32)
TINY_CELL = dict(batch=4, seq_len=16, valid_lengths=[8, 16], ring=2, trace_steps=4,
                 loss_band={"first": [4.0, 7.0]})


def test_manifest_names_units_and_arrows():
    metrics = MANIFEST["end_to_end"] + MANIFEST["per_layer"]
    cells = [w["name"] for w in MANIFEST["workloads"]]
    names = [m["name"] for m in metrics] + cells + [c["name"] for c in MANIFEST["configs"]]
    assert all(NAME.match(n) for n in names) and len(set(names)) == len(names)
    assert all(UNIT.match(m["unit"]) and m["better"] in ("lower", "higher") for m in metrics)
    assert sorted(MANIFEST["paths"]) == ["chipbench", "tests/chipbench_tests"]
    assert sum(w["chips"] == 4 for w in MANIFEST["workloads"]) <= 1
    for cell in cells:   # every cell: setup_s, another end-to-end metric, a per-layer one
        e2e = {m["name"] for m in run.metrics_of(MANIFEST, "end_to_end", cell)}
        assert "setup_s" in e2e and len(e2e) >= 2
        layer = run.metrics_of(MANIFEST, "per_layer", cell)
        assert layer and all(m["moves"] in e2e for m in layer)


@pytest.mark.parametrize("cell", [w["name"] for w in MANIFEST["workloads"]])
def test_cell_resolves_to_its_files(cell):
    manifest, chips, cell_file, cfg, cfgmod = run.load_cell(cell)
    assert cell_file["name"] == cell and chips in (1, 4)
    assert {"batch", "ring", "loss_band", "why"} <= set(cell_file)
    assert cfg["throughput_metric"] in {m["name"] for m in
                                        run.metrics_of(manifest, "end_to_end", cell)}
    assert all(hasattr(cfgmod, f) for f in ("build", "make_ring", "flops_per_step",
                                            "FLOP_CONVENTION"))
    for m in run.metrics_of(manifest, "per_layer", cell):
        reader = run.load_py(os.path.join(run.HERE, "layer_metrics", m["name"] + ".py"))
        assert callable(reader.read)


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
    assert made.busy_share_pct(trace.is_custom_call, 0) == \
        pytest.approx(want["custom_call_busy_share_pct"])
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
    def reader(name):
        return run.load_py(os.path.join(run.HERE, "layer_metrics", name + ".py"))
    plain = trace.Trace([[("fusion f32[8]", 0.0, 1.0)]], [])
    assert reader("pallas_time_pct.img").read(plain, plain.spans, {}) is None
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
    assert set(out["metrics"]) == {"tokens_per_s", "step_ms_p95", "setup_s"}
    assert all(v["value"] > 0 for v in out["metrics"].values())
    assert out["device"]["platform"] == "cpu"
    earlier = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert earlier["compiles_in_window"] == 0 and earlier["mfu_bf16"] > 0
