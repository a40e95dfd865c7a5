"""CPU-only checks of the kimi_linear_48b_a3b configuration and its cell: the
configuration file against the published config, the manifest's entries and
both new readers, weights and first loss that do not follow `--seed`, a toy
rehearsal of a whole run, the benchmark's copy of the reference, the FLOP and
byte counts worked by hand, and the KDA readers on a hand-made trace.

What must hold of the manifest is in the functions of MANIFEST_CHECKS, each of
one manifest, which the rehearsal of an addition (test_chipbench_harness.py)
also runs on the manifest plus appended entries: presence, fields and
supersets, never a position, a length or an exact set of a shared list."""
import json
import os

import numpy as onp
import pytest

import manifest_checks
from chipbench import run, trace

CELL = "kimi_linear_48b_a3b.sft_t16384_ep32share"
TINY_CFG = dict(
    hidden_size=32, intermediate_size=48, vocab_size=64, num_hidden_layers=5,
    linear_attn_config={"kda_layers": [1, 2, 3, 5], "full_attn_layers": [4], "num_heads": 2,
                        "head_dim": 16, "short_conv_kernel_size": 4},
    num_attention_heads=2, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    kv_lora_rank=12, num_experts=2, num_experts_routed=8, ep_rank=1, num_experts_per_token=2,
    moe_intermediate_size=16, dtype="float32", optimizer_params={"learning_rate": 1e-2})
TINY_CELL = dict(batch=1, seq_len=48, ring=4, trace_steps=3, loss_band={"first": [3.5, 5.0]})
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


@pytest.fixture(scope="module")
def loaded():
    return run.load_cell(CELL)


def reader(name):
    return run.load_py(os.path.join(run.HERE, "layer_metrics", name + ".py"))


def the_configurations_entry(manifest):
    """The configuration and its cell are listed once each, with the cut the
    configuration's file states and the cell's share of the deployment."""
    entry = manifest_checks.listed_once(manifest["configs"], "kimi_linear_48b_a3b")
    cfg = run.load_json(run.ROOT, entry["file"])
    assert entry["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    assert [r.split()[0] for r in cfg["reduced"]] == entry["reduced"]
    workload = manifest_checks.listed_once(manifest["workloads"], CELL)
    assert "32 times" in workload["why"] and "512" in workload["why"]


def the_cells_metrics(manifest):
    """The cell is listed under every metric it reports: a superset, since a
    later addition may list it under more; KDA's entries keep their fields."""
    ends = manifest_checks.end_to_end_of(manifest, CELL)
    assert ends >= {"tokens_per_s", "step_ms_p95", "setup_s"}
    layers = {m["name"] for m in run.metrics_of(manifest, "per_layer", CELL)}
    assert layers >= {
        "dispatch_ms.tok", "device_idle_pct.tok", "setup_compile_s", "setup_programs",
        "setup_step_programs", "setup_initialize_s", "step_mfu_pct.tok",
        "flash_roofline_pct.tok", "moe_experts_roofline_pct.tok", "moe_load_max_over_mean.tok",
        "kda_roofline_pct.tok", "kda_step_share_pct.tok", "gmm_kernel_path_pct.tok",
        "kda_kernel_path_pct.tok", "setup_import_s", "setup_trace_s", "setup_lower_s",
        "setup_backend_s", "setup_cache_misses", "setup_settle_s", "setup_trace_self_max_s",
        "setup_unaccounted_s"}
    for name, better in (("kda_roofline_pct.tok", "higher"), ("kda_step_share_pct.tok", "lower")):
        entry = manifest_checks.entry(manifest, name)
        assert CELL in entry["workloads"] and entry["moves"] == "tokens_per_s"
        assert entry["source"] == "device_trace" and entry["better"] == better
        assert entry["layer"] == manifest_checks.OPS_LAYER
        assert callable(reader(name).read)


MANIFEST_CHECKS = (the_configurations_entry, the_cells_metrics)


PUBLISHED = dict(
    hidden_size=2304, intermediate_size=9216, moe_intermediate_size=1024, kv_lora_rank=512,
    qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128, num_attention_heads=32,
    num_key_value_heads=32, head_dim=72, num_experts_per_token=8, num_shared_experts=1,
    routed_scaling_factor=2.446, moe_router_activation_func="sigmoid", moe_renormalize=True,
    num_expert_group=1, topk_group=1, first_k_dense_replace=1, rms_norm_eps=1e-05,
    mla_use_nope=True, q_lora_rank=None, rope_theta=10000, tie_word_embeddings=False,
    model_max_length=1048576, moe_layer_freq=1, use_grouped_topk=True, hidden_act="silu",
    num_nextn_predict_layers=0, rope_scaling=None, model_type="kimi_linear")


@pytest.mark.parametrize("key", sorted(PUBLISHED))
def test_configuration_keeps_the_published_value(loaded, key):
    assert loaded[3][key] == PUBLISHED[key]


def test_configuration_states_its_cut(loaded):
    manifest, chips, cell, cfg, mod = loaded
    the_configurations_entry(manifest)
    assert (cfg["num_hidden_layers"], cfg["num_experts"], cfg["vocab_size"]) == (5, 8, 20480)
    assert cfg["published"] == {"num_hidden_layers": 27, "num_experts": 256,
                                "vocab_size": 163840}
    assert cfg["num_experts_routed"] == 256 and cfg["ep_rank"] in range(32)
    lin = cfg["linear_attn_config"]
    assert (lin["num_heads"], lin["head_dim"], lin["short_conv_kernel_size"]) == (32, 128, 4)
    assert lin["full_attn_layers"] == [4, 8, 12, 16, 20, 24, 27] and len(lin["kda_layers"]) == 20
    assert sorted(lin["kda_layers"] + lin["full_attn_layers"]) == list(range(1, 28))
    assert mod.layer_kinds(cfg) == [("kda", "dense"), ("kda", "sparse"), ("kda", "sparse"),
                                    ("latent_attention", "sparse"), ("kda", "sparse")]
    assert {"deployment", "assumed", "departures", "weights_seed"} <= set(cfg)
    assert "32 chips" in cfg["deployment"] and cfg["dtype"] == "bfloat16" and cfg["remat"]
    assert chips == 1 and (cell["batch"], cell["seq_len"], cell["ring"]) == (1, 16384, 4)
    assert cell["trace_steps"] == 6 and "32 times" in cell["why"] and "512" in cell["why"]
    lo, hi = cell["loss_band"]["first"]
    assert hi - lo == pytest.approx(0.004, abs=1e-6) and 10.38 < lo < 10.3821 < hi
    assert cell["memory"]["chip"]["timed_program"]["temp_bytes"] \
        == cell["memory"]["described_chip_compile"]["temp_bytes"]
    assert len(cell["kda_result_shapes"]) >= 2 and cell["flash_result_shapes"] == []


def test_the_manifest_lists_the_cell_under_the_metrics_it_reports(loaded):
    the_cells_metrics(loaded[0])


def test_the_two_copies_of_the_reference_agree():
    here = os.path.join(run.HERE, "configs", "kimi_linear_48b_a3b_reference.py")
    there = os.path.join(run.ROOT, "mxnet_tpu", "models", "reference", "kimi_linear.py")
    with open(here) as a, open(there) as b:
        assert a.read() == b.read()


def test_weights_and_the_first_batch_do_not_follow_the_seed(loaded):
    import mxnet_tpu as mx
    _m, _c, cell, cfg, mod = loaded
    assert cfg["optimizer_params"] == {"learning_rate": 1e-4}      # ISSUE 34's
    cfg, cell = dict(cfg, **TINY_CFG), dict(cell, **TINY_CELL)
    weights = []
    for seed in (1, 2 ** 31 + 7):
        mx.random.seed(seed)                      # what the runner does first
        block, _trainer = mod.build(cfg)
        weights.append({k: p.data().asnumpy() for k, p in block.collect_params().items()})
    assert all(onp.array_equal(weights[0][k], weights[1][k]) for k in weights[0])
    assert any(v.std() > 0 for v in weights[0].values())
    # every parameter is trained but the correction bias and the load counter
    frozen = {k for k, p in block.collect_params().items() if p.grad_req == "null"}
    assert frozen and all(k.endswith(("correction_bias", "expert_load")) for k in frozen)
    assert all(p.lr_mult == 1.0 for p in block.collect_params().values())
    rings = {seed: [x[0][0].asnumpy() for x in mod.make_ring(cfg, cell, 1, seed, None)]
             for seed in (1, 2, 2 ** 31 + 7)}
    first = rings[1]
    for seed, ring in rings.items():  # batch 0 is the file's, batches 1-3 the seed's
        assert onp.array_equal(ring[0], first[0])
        if seed != 1:
            assert not any(onp.array_equal(x, y) for x in ring[1:] for y in first)
    again = mod.make_ring(cfg, cell, 1, 1, None)
    assert all(onp.array_equal(x[0][0].asnumpy(), y) for x, y in zip(again, first))
    assert [w for _args, w in again] == [48] * 4
    ids = onp.concatenate([x[0][0].asnumpy().ravel() for x in again])
    assert ids.min() >= 0 and ids.max() < cfg["vocab_size"]


FIRST_LOSS = {}


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
    assert earlier["warmup_losses"][0] == pytest.approx(
        FIRST_LOSS.setdefault("v", earlier["warmup_losses"][0]), abs=1e-6)
    # each KDA layer's trace left its span, with the tokens it saw
    from mxnet_tpu import observe
    traced = [s for s in observe.spans() if s["name"] == "kda.trace"]
    assert len(traced) >= 4 and traced[-1]["args"]["tokens"] == 48


def test_the_build_records_the_layer_kinds(loaded):
    from mxnet_tpu import observe
    _m, _c, _cell, cfg, mod = loaded
    mod.build(dict(cfg, **TINY_CFG))
    spans = [s for s in observe.spans() if s["name"] == "decoder.build"]
    assert spans and spans[-1]["args"]["layers"] == 5
    assert spans[-1]["args"]["kinds"] == ("kda+dense,kda+sparse,kda+sparse,"
                                          "latent_attention+sparse,kda+sparse")


def test_work_counts_worked_by_hand(loaded):
    _m, _c, cell, cfg, mod = loaded
    # at a tiny size: C = 64, one head of 16, T = 128 -> 2 chunks
    tiny = dict(cfg, **TINY_CFG)
    tiny_cell = dict(cell, batch=1, seq_len=128)
    per_chunk = 64 * 64 * (16 + 16) + 3 * 64 * 16 * 16          # multiply-adds, forward
    assert mod.kda_flops(tiny, tiny_cell, 1) == 3 * 2 * per_chunk * 2 * 2 * 4   # 2 chunks, 2 heads, 4 layers
    per_token_head = (3 * 16 * 2 + 16 * 4 + 4)                  # q, k, v bf16; g f32; beta f32
    assert mod.kda_bytes(tiny, tiny_cell, 1) == \
        ((per_token_head + 32) * 2 + per_token_head) * 2 * 128 * 4
    # MLA: q.k 24 wide, v 16: forward 2*24 + 2*16, backward 4*16 + 4*24 per score and head
    assert mod.attention_flops(tiny, tiny_cell, 1) == (80 + 160) * 2 * (128 * 129 // 2)
    assert mod.attention_bytes(tiny, tiny_cell, 1) == \
        2 * ((48 + 32) + (48 + 48) + (48 + 16)) * 2 * 128
    # at the published widths
    assert mod.dense_parameters(cfg) == 4 * 39460864 + 29114368 + 4 * (589824 + 7077888) \
        + 63700992 + 2304 * 20480
    assert mod.dense_flops(cfg, cell, 1) == 6.0 * 328515584 * 16384
    assert mod.attention_flops(cfg, cell, 1) == 1920.0 * 32 * (16384 * 16385 // 2)
    assert mod.kda_flops(cfg, cell, 1) == 3 * 2 * 4194304 * 256 * 32 * 4
    assert mod.kda_bytes(cfg, cell, 1) == 4364.0 * 32 * 16384 * 4
    assert mod.balanced_rows(cfg, cell, 1) == 16384 * 8 * 4 // 32       # 512 an expert
    assert mod.expert_flops(cfg, 1) == 6 * 3 * 2304 * 1024
    step = mod.flops_per_step(cfg, cell, 1, None)
    assert 2.4e9 < step / 16384 < 2.7e9                       # about 2.55 GFLOP a token trained


def hand_made_trace(core_s, steps=2):
    """A step: KDA's scan over the groups as a `while` spanning two rows inside
    it, a relayout to chunk-major outside it, one flash call, one unrelated
    fusion (labels as the cell's traced run has them)."""
    ops, spans, t = [], [], 0.0
    for _ in range(steps):
        spans.append(("chipbench.dispatch", t, t + 0.001))
        ops += [("while (s32[], f32[1,32,128,128], bf16[4,64,1,32,64,128], bf16[4,64,1,32,64,128]",
                 t, t + core_s / 2),
                ("multiply_reduce_fusion f32[64,32,4,16,128]", t + 0.01 * core_s, t + 0.2 * core_s),
                ("bitcast_add_fusion f32[1,32,128,128]", t + 0.2 * core_s, t + 0.3 * core_s),
                ("copy bf16[4,64,1,32,64,128]", t + 1, t + 1 + core_s / 2),
                ("tpu_custom_call:flash_fwd (bf16[32,16384,128], f32[32,16384,1])", t + 2, t + 2.5),
                ("fusion f32[8]", t + 3, t + 3.5 - core_s)]
        spans.append(("chipbench.wait", t + 3.4, t + 4.0))
        t += 4.0
    return trace.Trace([ops], spans)


def test_kda_readers_on_a_hand_made_trace(loaded, monkeypatch):
    monkeypatch.setattr(run, "peaks_of", lambda kind: dict(PEAK))
    _m, _c, cell, cfg, mod = loaded
    least = mod.kda_bytes(cfg, cell, 1) / PEAK["hbm_bytes_per_s"]
    assert least > mod.kda_flops(cfg, cell, 1) / PEAK["bf16_flops_per_s"]    # HBM-bound
    made = hand_made_trace(0.5)
    # the `while` and the rows inside it are counted once: 0.25 + 0.25 s a step
    assert reader("kda_roofline_pct.tok").read(made, made.spans, cell) \
        == pytest.approx(100.0 * least / 0.5)
    # busy a step: 0.5 (core) + 0.5 (flash) + 0.0 (3.0 .. 3.0): core is half
    assert reader("kda_step_share_pct.tok").read(made, made.spans, cell) == pytest.approx(50.0)
    # a Mosaic kernel named kda_* is found by its name, whatever its shape
    named = trace.Trace([[("tpu_custom_call:kda_fwd bf16[7]", 0.0, 0.25),
                          ("tpu_custom_call:flash_fwd bf16[7]", 0.25, 1.0)]],
                        [("chipbench.dispatch", 0.0, 0.1)])
    assert reader("kda_step_share_pct.tok").read(named, named.spans, cell) == pytest.approx(25.0)
    assert reader("kda_roofline_pct.tok").read(named, named.spans, cell) \
        == pytest.approx(100.0 * least / 0.25)
    # nothing to read: nothing returned, nothing raised (a program without the layer)
    plain = trace.Trace([[("fusion f32[8]", 0.0, 1.0)]], [("chipbench.dispatch", 0.0, 0.1)])
    assert reader("kda_roofline_pct.tok").read(plain, plain.spans, cell) is None
    assert reader("kda_step_share_pct.tok").read(plain, plain.spans, cell) is None
    assert reader("kda_roofline_pct.tok").read(made, made.spans,
                                               dict(cell, kda_result_shapes=[])) is None
    # a cell of a configuration without KDA reads nothing either
    mellum = run.load_cell("mellum2_12b_a2p5b.sft_t8192_ep4share")[2]
    assert reader("kda_roofline_pct.tok").read(made, made.spans, mellum) is None


def test_the_reused_readers_read_this_cell(loaded, monkeypatch):
    monkeypatch.setattr(run, "peaks_of", lambda kind: dict(PEAK))
    _m, _c, cell, cfg, mod = loaded
    made = hand_made_trace(0.5, steps=4)
    least = mod.attention_flops(cfg, cell, 1) / PEAK["bf16_flops_per_s"]
    assert least > mod.attention_bytes(cfg, cell, 1) / PEAK["hbm_bytes_per_s"]   # FLOP-bound
    assert reader("flash_roofline_pct.tok").read(made, made.spans, cell) \
        == pytest.approx(100.0 * least / 0.5)
    mfu = reader("step_mfu_pct.tok").read(made, made.spans, cell)
    assert mfu == pytest.approx(
        100.0 * mod.flops_per_step(cfg, cell, 1, None) / 4.0 / PEAK["bf16_flops_per_s"])
