"""CPU-only checks of the xing4_29b_a4b configuration and its cell, and of the
bert_base.phase2_t512 cell: the configuration file against the published
config, the cut and its parameter count, the manifest's entries and every
invariant of manifest_checks on the real manifest, a ring that does not follow
`--seed`, a toy rehearsal of a whole run, the benchmark's copy of the
reference, mHC's FLOP and byte counts worked by hand, and the two mHC readers
on a hand-made trace (mhc_trace_fixture.json).

What must hold of the manifest is in the functions of MANIFEST_CHECKS, each of
one manifest: the tests here run them on BENCHMARK.json, and the rehearsal of
an addition (test_chipbench_harness.py) on the manifest plus appended entries.
They state presence, fields and supersets, never a position, a length or an
exact set of a list that later additions append to."""
import json
import os

import numpy as onp
import pytest

import manifest_checks
from chipbench import run, trace

CELL = "xing4_29b_a4b.sft_t8192_ep8share"
BERT2 = "bert_base.phase2_t512"
HERE = os.path.dirname(os.path.abspath(__file__))
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
TINY_CFG = dict(
    hidden_size=64, intermediate_size=96, moe_intermediate_size=32, kv_lora_rank=12,
    q_lora_rank=24, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    num_attention_heads=4, n_routed_experts=2, router_experts=8, ep_rank=1,
    num_experts_per_tok=2, vocab_size=97, dtype="float32", layers_held=[0, 2, 3],
    optimizer_params={"learning_rate": 1e-2})
TINY_CELL = dict(batch=1, seq_len=32, ring=4, trace_steps=3, loss_band={"first": [4.0, 6.5]})

# the catalog row's `config`, as config.json publishes it
PUBLISHED = {
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 2, "hidden_act": "silu",
    "hidden_size": 3584, "intermediate_size": 9216, "kv_lora_rank": 512,
    "max_position_embeddings": 262144, "model_type": "xing4_0", "moe_intermediate_size": 1024,
    "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 64, "n_shared_experts": 1,
    "norm_topk_prob": True, "num_attention_heads": 32, "num_experts_per_tok": 4,
    "num_hidden_layers": 40, "num_key_value_heads": 32, "num_nextn_predict_layers": 1,
    "hc_mult": 4, "hc_sinkhorn_iters": 20, "hc_eps": 1e-06, "mhc_h_res_clamp_min": -30,
    "mhc_h_res_clamp_max": 30, "q_lora_rank": 768, "qk_nope_head_dim": 128,
    "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06, "rope_theta": 10000,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 64, "mscale": 1,
                     "mscale_all_dim": 1, "original_max_position_embeddings": 4096,
                     "type": "yarn"},
    "routed_scaling_factor": 2, "scoring_func": "sigmoid", "tie_word_embeddings": False,
    "topk_group": 1, "topk_method": "noaux_tc", "v_head_dim": 128, "vocab_size": 131072}
REDUCED = {"num_hidden_layers": 5, "n_routed_experts": 8, "vocab_size": 16384}


@pytest.fixture(scope="module")
def loaded():
    return run.load_cell(CELL)


def reader(name):
    return run.load_py(os.path.join(run.HERE, "layer_metrics", name + ".py"))


def the_configurations_entry(manifest):
    """The configuration is listed once, with the cut and the source its file states."""
    entry = manifest_checks.listed_once(manifest["configs"], "xing4_29b_a4b")
    cfg = run.load_json(run.ROOT, entry["file"])
    assert entry["reduced"] == ["num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert [r.split()[0] for r in cfg["reduced"]] == entry["reduced"]
    assert entry["source"] == cfg["source"]
    manifest_checks.one_line(entry, "why", "source")


def the_cells_entries(manifest):
    """Both cells are listed once each, on one chip, with a one-line `why`."""
    xing = manifest_checks.listed_once(manifest["workloads"], CELL)
    bert = manifest_checks.listed_once(manifest["workloads"], BERT2)
    assert xing["config"] == "xing4_29b_a4b" and xing["chips"] == 1 and "512" in xing["why"]
    assert bert["config"] == "bert_base" and bert["chips"] == 1
    for entry in (xing, bert):
        manifest_checks.one_line(entry, "why")


def the_cells_metrics(manifest):
    """Each cell is listed under every metric it reports: a superset, since a
    later addition may list it under more; the mHC entries keep their fields."""
    tok = {"tokens_per_s", "step_ms_p95", "setup_s"}
    for cell in (CELL, BERT2):
        assert manifest_checks.end_to_end_of(manifest, cell) >= tok, cell
    layers = {m["name"] for m in run.metrics_of(manifest, "per_layer", CELL)}
    assert layers >= set(manifest_checks.SETUP) | {
        "setup_compile_s", "setup_programs", "setup_step_programs", "setup_initialize_s",
        "dispatch_ms.tok", "device_idle_pct.tok", "step_mfu_pct.tok", "flash_roofline_pct.tok",
        "moe_experts_roofline_pct.tok", "moe_load_max_over_mean.tok", "gmm_kernel_path_pct.tok",
        "mhc_step_share_pct.tok", "mhc_roofline_pct.tok", "mhc_kernel_path_pct.tok"}
    for name, better, source in (("mhc_step_share_pct.tok", "lower", "device_trace"),
                                 ("mhc_roofline_pct.tok", "higher", "device_trace"),
                                 ("mhc_kernel_path_pct.tok", "higher", "program_counter")):
        entry = manifest_checks.entry(manifest, name)
        assert CELL in entry["workloads"] and entry["moves"] == "tokens_per_s"
        assert entry["source"] == source and entry["better"] == better
        assert entry["layer"] == manifest_checks.OPS_LAYER and entry["unit"] == "%"
    # phase 2 is read as phase 1 is: the step's, the device's and set-up's readers
    bert = set(manifest_checks.SETUP) | {"dispatch_ms.tok", "device_idle_pct.tok"} | {
        n for n in manifest_checks.PROGRAM if not n.endswith(".img")}
    for cell in ("bert_base.phase1_t128", BERT2):
        assert {m["name"] for m in run.metrics_of(manifest, "per_layer", cell)} >= bert, cell


MANIFEST_CHECKS = (the_configurations_entry, the_cells_entries, the_cells_metrics)


@pytest.mark.parametrize("key", sorted(PUBLISHED))
def test_configuration_keeps_the_published_value(loaded, key):
    cfg = loaded[3]
    assert cfg[key] == REDUCED.get(key, PUBLISHED[key])
    if key in REDUCED:
        assert cfg["published"][key] == PUBLISHED[key]


def test_configuration_states_its_cut(loaded):
    manifest, chips, cell, cfg, mod = loaded
    the_configurations_entry(manifest)
    assert cfg["router_experts"] == 64 and cfg["ep_rank"] in range(8)
    assert cfg["layers_held"] == [0, 2, 3, 4, 5]        # the dense layer once, four routed
    assert mod.layer_kinds(cfg) == [("latent_attention", "dense")] + \
        [("latent_attention", "sparse")] * 4
    assert {"deployment", "assumed", "departures", "weights_seed"} <= set(cfg)
    assert "8 chips" in cfg["deployment"] and cfg["dtype"] == "bfloat16" and cfg["remat"]
    assert {"mhc_norm", "mhc_init", "hc_eps", "mtp_loss_weight", "mtp_norms"} <= set(
        cfg["assumed"])
    assert any("rotate-half" in d for d in cfg["departures"])
    assert chips == 1 and (cell["batch"], cell["seq_len"], cell["ring"]) == (1, 8192, 4)
    assert cell["trace_steps"] == 6 and "512" in cell["why"]
    assert cell["mhc_result_shapes"] and cell["flash_result_shapes"] == []


def test_the_cut_holds_the_stated_parameters(loaded):
    """The model at published widths, counted from its parameters' shapes
    before any is made: 1 dense + 4 routed layers + the MTP module + the
    vocabulary slice, 913,645,700 parameters (the correction biases counted;
    the load counters and the MTP loss state are not parameters)."""
    from mxnet_tpu.models import DecoderLM
    _m, _c, _cell, cfg, mod = loaded
    model = DecoderLM(**mod.model_arguments(cfg))
    sizes = {k: int(onp.prod(p.shape)) for k, p in model.collect_params().items()}
    counted = sum(v for k, v in sizes.items() if not k.endswith(("expert_load", "mtp_loss")))
    assert counted == 913645700
    assert sum(v for k, v in sizes.items() if k.startswith("mtp.")
               and not k.endswith(("expert_load", "mtp_loss"))) == 154155894
    assert sizes["layer1.attend.hc.phi"] == 14336 * 24
    assert sizes["layer0.attend.attention.q_a.weight"] == 3584 * 768


def test_the_manifest_lists_the_cells_under_the_metrics_they_report(loaded):
    the_cells_metrics(loaded[0])


def test_every_check_passes_on_the_real_manifest():
    manifest_checks.every_check(run.load_json(run.ROOT, "BENCHMARK.json"))


def test_the_new_entries_were_appended(loaded):
    """The configuration and both cells are there once each; the per-layer
    entries that came with them follow those that were there before, in the
    order they came (manifest_checks.APPENDED)."""
    manifest = loaded[0]
    the_configurations_entry(manifest)
    the_cells_entries(manifest)
    manifest_checks.order_kept(manifest)


def test_the_bert_phase2_cell_is_phase1_at_512(loaded):
    _m, chips, cell, cfg, _mod = run.load_cell(BERT2)
    first = run.load_cell("bert_base.phase1_t128")[2]
    assert chips == 1 and cell["seq_len"] == 512 and cell["valid_lengths"] == [256, 512]
    assert cell["batch"] in (16, 32) and cell["ring"] == 4 and cell["trace_steps"] == 20
    assert cell["loss_band"]["first"] == first["loss_band"]["first"]
    assert cfg["use_flash"] == "auto" and 512 < 1024      # dense attention at this length


def test_the_two_copies_of_the_reference_agree():
    here = os.path.join(run.HERE, "configs", "xing4_29b_a4b_reference.py")
    there = os.path.join(run.ROOT, "mxnet_tpu", "models", "reference", "xing4.py")
    with open(here) as a, open(there) as b:
        assert a.read() == b.read()


def test_weights_and_the_whole_ring_do_not_follow_the_seed(loaded):
    import mxnet_tpu as mx
    _m, _c, cell, cfg, mod = loaded
    assert cfg["optimizer_params"] == {"learning_rate": 1e-4}
    cfg, cell = dict(cfg, **TINY_CFG), dict(cell, **TINY_CELL)
    weights = []
    for seed in (1, 2 ** 31 + 7):
        mx.random.seed(seed)                      # what the runner does first
        block, _trainer = mod.build(cfg)
        weights.append({k: p.data().asnumpy() for k, p in block.collect_params().items()})
    assert all(onp.array_equal(weights[0][k], weights[1][k]) for k in weights[0])
    frozen = {k for k, p in block.collect_params().items() if p.grad_req == "null"}
    assert frozen and all(k.endswith(("correction_bias", "expert_load", "mtp_loss"))
                          for k in frozen)
    rings = [[x[0][0].asnumpy() for x in mod.make_ring(cfg, cell, 1, seed, None)]
             for seed in (1, 2, 2 ** 31 + 7)]
    for ring in rings[1:]:                        # every batch, in order, the file's
        assert all(onp.array_equal(a, b) for a, b in zip(ring, rings[0]))
    assert not any(onp.array_equal(rings[0][i], rings[0][j])
                   for i in range(4) for j in range(i))
    assert [w for _args, w in mod.make_ring(cfg, cell, 1, 1, None)] == [32] * 4


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
    assert earlier["warmup_losses"][0] == pytest.approx(
        FIRST_LOSS.setdefault("v", earlier["warmup_losses"][0]), abs=1e-6)
    from mxnet_tpu import observe
    spans = observe.spans()
    # two a traced layer: the dense one and the first routed one, whose trace
    # the later routed layers and the MTP module's share
    assert sum(s["name"] == "mhc.trace" for s in spans) >= 4
    assert any(s["name"] == "mtp.trace" and s["args"]["tokens"] == 32 for s in spans)


def test_mhc_work_counted_by_hand(loaded):
    _m, _c, cell, cfg, mod = loaded
    tiny, tiny_cell = dict(cfg, **TINY_CFG), dict(cell, batch=1, seq_len=16)
    # n = 4, U = 64: the flat row 256 wide, 24 logits; 8 sublayers (3 layers + MTP)
    forward = 2 * 256 * 24 + 4 * 256 + 2 * 256 + 2 * 16 * 64 + 2 * 256 + 2 * 20 * 2 * 16
    assert forward == 17664
    assert mod.mhc_flops(tiny, tiny_cell, 1) == 3 * 17664 * 8 * 16
    stream, row = 2 * 256, 2 * 64
    per_sublayer = (2 * stream + 2 * row) + (3 * stream + 2 * row)
    assert per_sublayer == 3072
    assert mod.mhc_bytes(tiny, tiny_cell, 1) == (3072 * 8 + 2 * 4 * stream) * 16
    # at the published widths: 12 sublayers of 8,192 tokens, HBM-bound
    assert mod.mhc_bytes(cfg, cell, 1) == (172032 * 12 + 2 * 4 * 28672) * 8192
    assert mod.mhc_bytes(cfg, cell, 1) / PEAK["hbm_bytes_per_s"] > \
        10 * mod.mhc_flops(cfg, cell, 1) / PEAK["bf16_flops_per_s"]
    # attention: six MLA layers at q.k 192, v 128
    assert mod.attention_flops(cfg, cell, 1) == 1920.0 * 32 * (8192 * 8193 // 2) * 6
    assert mod.balanced_rows(cfg, cell, 1) == 8192 * 4 * 5 // 8           # 512 an expert
    assert mod.dense_parameters(cfg) == 6 * 28409856 + 5 * (229376 + 11010048) \
        + 99090432 + 2 * 3584 * 3584 + 2 * 3584 * 16384
    step = mod.flops_per_step(cfg, cell, 1, None)
    assert 33e12 < step < 40e12                   # the model's ~34 TFLOP a step and more


def fixture_trace():
    made = run.load_json(HERE, "mhc_trace_fixture.json")
    ops, spans = [], []
    for k in range(made["steps"]):
        ops += [(label, s + k, e + k) for label, s, e in made["step"]]
        spans += [("chipbench.dispatch", k + 0.0, k + 0.01), ("chipbench.wait", k + 0.5, k + 0.95)]
    return made, trace.Trace([ops], spans)


def test_mhc_readers_on_a_hand_made_trace(loaded, monkeypatch):
    monkeypatch.setattr(run, "peaks_of", lambda kind: dict(PEAK))
    _m, _c, cell, cfg, mod = loaded
    made, tr = fixture_trace()
    cell = dict(cell, mhc_result_shapes=made["mhc_result_shapes"])
    least = mod.mhc_bytes(cfg, cell, 1) / PEAK["hbm_bytes_per_s"]
    share = reader("mhc_step_share_pct.tok").read(tr, tr.spans, cell)
    assert share == pytest.approx(made["expect"]["share_pct"])
    assert reader("mhc_roofline_pct.tok").read(tr, tr.spans, cell) == pytest.approx(
        100.0 * least / made["expect"]["mhc_s_per_step"])
    # nothing to read: nothing returned, nothing raised
    plain = trace.Trace([[("fusion f32[8]", 0.0, 1.0)]], [("chipbench.dispatch", 0.0, 0.1)])
    for name in ("mhc_step_share_pct.tok", "mhc_roofline_pct.tok"):
        assert reader(name).read(plain, plain.spans, cell) is None
    # a cell of a configuration without mHC (no shapes, no counts) reads nothing either
    kimi = run.load_cell("kimi_linear_48b_a3b.sft_t16384_ep32share")[2]
    assert reader("mhc_roofline_pct.tok").read(tr, tr.spans, kimi) is None or \
        "mhc_result_shapes" not in kimi
    only_named = dict(kimi, mhc_result_shapes=[])
    assert reader("mhc_roofline_pct.tok").read(tr, tr.spans, only_named) is None


def test_the_reused_readers_read_this_cell(loaded, monkeypatch):
    monkeypatch.setattr(run, "peaks_of", lambda kind: dict(PEAK))
    _m, _c, cell, cfg, mod = loaded
    _made, tr = fixture_trace()
    least = mod.attention_flops(cfg, cell, 1) / PEAK["bf16_flops_per_s"]
    assert least > mod.attention_bytes(cfg, cell, 1) / PEAK["hbm_bytes_per_s"]   # FLOP-bound
    assert reader("flash_roofline_pct.tok").read(tr, tr.spans, cell) == pytest.approx(
        100.0 * least / 0.4)
    mfu = reader("step_mfu_pct.tok").read(tr, tr.spans, cell)
    assert mfu is None or mfu > 0
