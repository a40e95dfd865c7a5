"""CPU-only checks of the mellum2_12b_a2p5b configuration and its cell: the
configuration file against the published config, weights and first loss
that do not follow `--seed`, a toy rehearsal of a whole run, the benchmark's
copy of the reference, the FLOP and byte counts worked by hand, and the
roofline readers on a hand-made trace.

What must hold of the manifest is in the functions of MANIFEST_CHECKS, each of
one manifest, which the rehearsal of an addition (test_chipbench_harness.py)
also runs on the manifest plus appended entries: presence, fields and
supersets, never a position, a length or an exact set of a shared list."""
import json
import os

import jax
import numpy as onp
import pytest

import manifest_checks
from chipbench import run, trace

CELL = "mellum2_12b_a2p5b.sft_t8192_ep4share"
TINY_CFG = dict(hidden_size=32, head_dim=8, num_attention_heads=4, num_key_value_heads=2,
                num_hidden_layers=4, sliding_window=6, num_experts=2, num_experts_routed=8,
                num_experts_per_tok=2, moe_intermediate_size=16, vocab_size=64, ep_rank=1,
                dtype="float32", optimizer_params={"learning_rate": 1e-2})
TINY_CELL = dict(batch=2, seq_len=16, ring=4, trace_steps=3, loss_band={"first": [3.5, 5.0]})
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


@pytest.fixture(scope="module")
def loaded():
    return run.load_cell(CELL)


def reader(name):
    return run.load_py(os.path.join(run.HERE, "layer_metrics", name + ".py"))


def the_configurations_entry(manifest):
    """The configuration is listed once, with the cut its file states."""
    entry = manifest_checks.listed_once(manifest["configs"], "mellum2_12b_a2p5b")
    cfg = run.load_json(run.ROOT, entry["file"])
    assert entry["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    assert [r.split()[0] for r in cfg["reduced"]] == entry["reduced"]


def the_cells_metrics(manifest):
    """The cell is listed once, on one chip, under every metric it reports: a
    superset, since a later addition may list it under more."""
    workload = manifest_checks.listed_once(manifest["workloads"], CELL)
    assert workload["chips"] == 1
    assert manifest_checks.end_to_end_of(manifest, CELL) >= {"tokens_per_s", "step_ms_p95",
                                                             "setup_s"}
    layers = {m["name"] for m in run.metrics_of(manifest, "per_layer", CELL)}
    assert layers >= set(manifest_checks.SETUP) | {
        "setup_compile_s", "setup_programs", "setup_step_programs", "setup_initialize_s",
        "dispatch_ms.tok", "device_idle_pct.tok", "step_mfu_pct.tok", "flash_roofline_pct.tok",
        "moe_experts_roofline_pct.tok", "moe_load_max_over_mean.tok", "gmm_kernel_path_pct.tok"}


MANIFEST_CHECKS = (the_configurations_entry, the_cells_metrics)


PUBLISHED = dict(hidden_size=2304, num_attention_heads=32, num_key_value_heads=4, head_dim=128,
                 moe_intermediate_size=896, num_experts_per_tok=8, sliding_window=1024,
                 intermediate_size=7168, rms_norm_eps=1e-06, max_position_embeddings=131072,
                 norm_topk_prob=True, tie_word_embeddings=False, attention_bias=False)


@pytest.mark.parametrize("key", sorted(PUBLISHED))
def test_configuration_keeps_the_published_value(loaded, key):
    assert loaded[3][key] == PUBLISHED[key]


def test_configuration_states_its_cut(loaded):
    manifest, chips, cell, cfg, _mod = loaded
    the_configurations_entry(manifest)
    assert (cfg["num_hidden_layers"], cfg["num_experts"], cfg["vocab_size"]) == (4, 16, 24576)
    assert cfg["published"] == {"num_hidden_layers": 28, "num_experts": 64, "vocab_size": 98304}
    assert cfg["num_experts_routed"] == 64 and cfg["ep_rank"] in range(4)
    assert cfg["layer_types"][:4] == ["sliding_attention"] * 3 + ["full_attention"]
    assert len(cfg["layer_types"]) == 28 and set(cfg["mlp_layer_types"]) == {"sparse"}
    rope = cfg["rope_parameters"]
    assert rope["sliding_attention"] == {"rope_type": "default", "rope_theta": 500000}
    assert rope["full_attention"]["factor"] == 16 and rope["full_attention"]["rope_type"] == "yarn"
    assert rope["full_attention"]["attention_factor"] == 1.2772588722239782
    assert {"deployment", "assumed", "departures", "weights_seed"} <= set(cfg)
    assert chips == 1 and (cell["batch"], cell["seq_len"], cell["ring"]) == (2, 8192, 4)
    assert "four times" in cell["why"]


def test_the_manifest_lists_the_cell_under_the_metrics_it_reports(loaded):
    the_cells_metrics(loaded[0])


def test_the_two_copies_of_the_reference_agree():
    here = os.path.join(run.HERE, "configs", "mellum2_12b_a2p5b_reference.py")
    there = os.path.join(run.ROOT, "mxnet_tpu", "models", "reference", "mellum2.py")
    with open(here) as a, open(there) as b:
        assert a.read() == b.read()


def test_weights_and_the_first_batch_do_not_follow_the_seed(loaded):
    import mxnet_tpu as mx
    _m, _c, cell, cfg, mod = loaded
    assert cfg["optimizer_params"] == {"learning_rate": 1e-4}      # ISSUE 32's
    cfg, cell = dict(cfg, **TINY_CFG), dict(cell, **TINY_CELL)
    weights = []
    for seed in (1, 2 ** 31 + 7):
        mx.random.seed(seed)                      # what the runner does first
        block, _trainer = mod.build(cfg)
        weights.append({k: p.data().asnumpy() for k, p in block.collect_params().items()})
    assert all(onp.array_equal(weights[0][k], weights[1][k]) for k in weights[0])
    assert any(v.std() > 0 for v in weights[0].values())
    # every parameter is trained as the deployment trains it, the router too
    assert all(p.lr_mult == 1.0 for p in block.collect_params().values())
    rings = {seed: [x[0][0].asnumpy() for x in mod.make_ring(cfg, cell, 1, seed, None)]
             for seed in (1, 2, 2 ** 31 + 7)}
    first = rings[1]
    moved = 0
    for seed, ring in rings.items():  # every batch is the file's; the seed orders the rows of 1-3
        assert onp.array_equal(ring[0], first[0])
        for x, y in zip(ring[1:], first[1:]):
            assert sorted(map(tuple, x)) == sorted(map(tuple, y))
            moved += not onp.array_equal(x, y)
    assert moved > 0
    a = mod.make_ring(cfg, cell, 1, 1, None)
    assert [onp.array_equal(x[0][0].asnumpy(), y) for x, y in zip(a, first)] == [True] * 4   # the same seed, the same ring
    assert [w for _args, w in a] == [32] * 4
    ids = onp.concatenate([x[0][0].asnumpy().ravel() for x in a])
    assert ids.min() >= 0 and ids.max() < cfg["vocab_size"]


@pytest.mark.parametrize("seed", [3, 2 ** 31 + 11])
def test_toy_rehearsal_of_a_whole_run(loaded, seed, monkeypatch, capsys):
    monkeypatch.setattr(run, "PLATFORM", "cpu")
    monkeypatch.setattr(run, "peaks_of", lambda kind: dict(PEAK))
    manifest, chips, cell, cfg, mod = loaded
    out = run.measure(CELL, manifest, chips, dict(cell, **TINY_CELL), dict(cfg, **TINY_CFG),
                      mod, seed=seed, seconds=0.3, traced=False)
    assert out["correct"] and out["failed"] == 0 and out["attempted"] >= 3
    assert set(out["metrics"]) == manifest_checks.end_to_end_of(manifest, CELL)
    earlier = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert earlier["compiles_in_window"] == 0 and earlier["mfu_bf16"] > 0
    # one number for every seed: the weights and ring batch 0 are the file's
    assert earlier["warmup_losses"][0] == pytest.approx(FIRST_LOSS.setdefault("v", earlier["warmup_losses"][0]), abs=1e-6)


FIRST_LOSS = {}


def test_work_counts_worked_by_hand(loaded):
    _m, _c, cell, cfg, mod = loaded
    assert mod.visible_scores(4, None) == 10 and mod.visible_scores(4, 2) == 1 + 2 + 2 + 2
    assert mod.visible_scores(8192, 1024) == 7864832          # 23.4% of the causal 33,558,528
    assert mod.layer_windows(cfg) == [1024, 1024, 1024, None]
    assert mod.balanced_rows(cfg, cell, 1) == 16384 * 8 * 4 // 4
    assert mod.expert_flops(cfg, 1) == 6 * 6193152
    assert mod.dense_flops(cfg, cell, 1) == 6.0 * (4 * 21381120 + 2304 * 24576) * 16384
    scores = 3 * 7864832 + 33558528
    assert mod.attention_flops(cfg, cell, 1) == 12.0 * 32 * 128 * 2 * scores
    step = mod.flops_per_step(cfg, cell, 1, None)
    assert 1.4e9 < step / 16384 < 1.6e9                       # about 1.5 GFLOP a token trained


def hand_made_trace(per_step_flash_s, per_step_experts_s, steps=2):
    ops, spans, t = [], [], 0.0
    for _ in range(steps):
        spans.append(("chipbench.dispatch", t, t + 0.001))
        ops += [("tpu_custom_call:flash_fwd (bf16[64,8192,128], f32[64,8192,1])", t, t + per_step_flash_s / 2),
                ("tpu_custom_call:custom-call bf16[64,8192,128]", t + 1, t + 1 + per_step_flash_s / 2),
                ("tpu_custom_call:ragged-dot-none bf16[40960,896]", t + 2, t + 2 + per_step_experts_s),
                ("fusion f32[8]", t + 3, t + 3.5)]
        spans.append(("chipbench.wait", t + 3.4, t + 4.0))
        t += 4.0
    return trace.Trace([ops], spans)


def test_flash_roofline_reader_on_a_hand_made_trace(loaded, monkeypatch):
    monkeypatch.setattr(run, "peaks_of", lambda kind: dict(PEAK))
    _m, _c, cell, cfg, mod = loaded
    least = mod.attention_flops(cfg, cell, 1) / PEAK["bf16_flops_per_s"]
    assert least > mod.attention_bytes(cfg, cell, 1) / PEAK["hbm_bytes_per_s"]   # FLOP-bound
    made = hand_made_trace(4 * least, 0.01)
    assert reader("flash_roofline_pct.tok").read(made, made.spans, cell) == pytest.approx(25.0)
    # the second call is found by its result shape alone, from the cell file
    assert reader("flash_roofline_pct.tok").read(made, made.spans, dict(cell, flash_result_shapes=[])) \
        == pytest.approx(50.0)
    plain = trace.Trace([[("fusion f32[8]", 0.0, 1.0)]], [("chipbench.dispatch", 0.0, 0.1)])
    assert reader("flash_roofline_pct.tok").read(plain, plain.spans, cell) is None
    assert reader("moe_experts_roofline_pct.tok").read(plain, plain.spans, cell) is None
    assert reader("step_mfu_pct.tok").read(plain, plain.spans, cell) is None


def test_expert_readers_read_the_programs_counters(loaded, monkeypatch):
    import mxnet_tpu as mx
    from mxnet_tpu.parallel import RoutedExperts, expert_loads
    monkeypatch.setattr(run, "peaks_of", lambda kind: dict(PEAK))
    _m, _c, cell, cfg, mod = loaded
    layer = RoutedExperts(8, 4, 8, 2, experts_held=4, ep_rank=1)
    layer.initialize()
    layer.expert_load.data()._rebind(jax.numpy.array([10, 30, 20, 20], "int32"))
    mine = [l for l in expert_loads() if l["rows"] == [10, 30, 20, 20]]
    assert mine and mine[0]["first_expert"] == 4
    made = hand_made_trace(1.0, 0.5, steps=4)
    ratio = reader("moe_load_max_over_mean.tok").read(made, made.spans, cell)
    assert ratio >= 30 * 4 / 80
    rows = sum(sum(l["rows"]) for l in expert_loads())
    least = max(mod.expert_flops(cfg, rows) / PEAK["bf16_flops_per_s"],
                mod.expert_bytes(cfg, rows) / PEAK["hbm_bytes_per_s"])
    got = reader("moe_experts_roofline_pct.tok").read(made, made.spans, cell)
    assert got == pytest.approx(100.0 * least / 0.5)
    mfu = reader("step_mfu_pct.tok").read(made, made.spans, cell)
    assert mfu == pytest.approx(100.0 * mod.flops_per_step(cfg, cell, 1, None) / 4.0 / PEAK["bf16_flops_per_s"])
    from mxnet_tpu import observe
    assert any(e[4:6] == ("moe", "moe.load") and e[6]["rows"] == [10, 30, 20, 20]
               for e in observe.events())
    del layer
