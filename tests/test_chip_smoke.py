"""`chip_smoke.py` is the bring-up check a builder sends to the chip; what
can be rehearsed of it on the CPU is here, so a call is not spent on a typo."""
import math
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_import_touches_no_device():
    """Importing the script loads neither jax nor the package: a parent
    that imports it (a test, a launcher) still leaves the chip free."""
    code = ("import sys, chip_smoke; "
            "assert 'jax' not in sys.modules and 'mxnet_tpu' not in sys.modules")
    r = subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr


def test_bert_flash_builder_runs_a_finite_step():
    """The BERT phase's model is `chipbench`'s bert_base held to the flash
    kernel (interpreted here): two layers, a small vocabulary, T=64, one
    ragged batch."""
    import chip_smoke

    step, batch = chip_smoke.bert_flash_step(0, 2, 64, num_hidden_layers=2,
                                             vocab_size=1024)
    assert [a.shape for a in batch] == [(2, 64)] * 4
    lengths = batch[3].asnumpy().sum(axis=1)
    assert ((32 <= lengths) & (lengths <= 64)).all() and lengths.min() < 64
    assert math.isfinite(float(step(*batch, batch_size=2).asnumpy()))


def test_decoder_reference_phase_at_toy_widths():
    """`--decoder-reference` compares the cell's own build with the plain
    reference; in f32 at toy widths the two agree closely, logits and
    gradients, and the fp8 control stands far outside that agreement."""
    import chip_smoke
    from chipbench import run

    _manifest, _chips, cell, cfg, cfgmod = run.load_cell(chip_smoke.DECODER_CELL)
    cfg.update(hidden_size=32, head_dim=8, num_attention_heads=4,
               num_key_value_heads=2, sliding_window=6, num_experts=4,
               num_experts_routed=16, num_experts_per_tok=2, ep_rank=1,
               moe_intermediate_size=16, vocab_size=64, dtype="float32")
    cell.update(batch=2, seq_len=16)
    out = chip_smoke.decoder_reference_numbers(cfg, cell, cfgmod, block=4)
    assert out["tokens"] == 16 and out["logits_rms"] > 0
    assert out["logits_err"]["max"] < 1e-4
    assert out["fp8_control_logits_err"]["rms"] > 30 * out["logits_err"]["rms"]
    assert abs(out["loss_system"] - out["loss_reference_f32"]) < 1e-4
    assert sorted(out["gradients"]) == sorted(chip_smoke.DECODER_GRADS)
    for name, g in out["gradients"].items():
        assert g["rel_l2"] < 2e-3 and g["cosine"] > 0.9999, (name, g)


def test_decoder_reference_phase_takes_the_kimi_linear_configuration():
    """`--decoder-reference kimi_linear_48b_a3b` at toy widths in f32: the
    system's five kinds of layer against the literal recurrence, dense MLA
    and the looped experts, given the system's picks; the fp8 control stands
    far outside that agreement."""
    import chip_smoke
    from chipbench import run

    spec = chip_smoke.DECODER_REFERENCES["kimi_linear_48b_a3b"]
    _manifest, _chips, cell, cfg, cfgmod = run.load_cell(spec["cell"])
    cfg.update(hidden_size=32, intermediate_size=48, vocab_size=64,
               linear_attn_config={"kda_layers": [1, 2, 3, 5], "full_attn_layers": [4],
                                   "num_heads": 2, "head_dim": 16,
                                   "short_conv_kernel_size": 4},
               num_attention_heads=2, qk_nope_head_dim=16, qk_rope_head_dim=8,
               v_head_dim=16, kv_lora_rank=12, num_experts=4, num_experts_routed=16,
               ep_rank=1, num_experts_per_token=2, moe_intermediate_size=16,
               dtype="float32")
    cell.update(batch=2, seq_len=32)
    out = chip_smoke.decoder_reference_numbers(cfg, cell, cfgmod, block=8,
                                               config="kimi_linear_48b_a3b")
    assert out["tokens"] == 32 and out["logits_rms"] > 0
    assert out["logits_err"]["max"] < 1e-4
    assert out["fp8_control_logits_err"]["rms"] > 30 * out["logits_err"]["rms"]
    assert abs(out["loss_system"] - out["loss_reference_f32"]) < 1e-4
    assert sorted(out["gradients"]) == sorted(n for n, *_ in spec["grads"])
    for name, g in out["gradients"].items():
        assert g["rel_l2"] < 3e-3 and g["cosine"] > 0.9999, (name, g)


def test_decoder_reference_phase_takes_the_xing4_configuration():
    """`--decoder-reference xing4_29b_a4b` at toy widths in f32: the system's
    mHC streams, q-latent rotary MLA, routed layers and MTP head against the
    plain reference given the system's picks, both heads' logits and the
    gradients of a mix's phi, q_a, kv_a, a router and the held experts' gate;
    the fp8 control stands far outside that agreement."""
    import chip_smoke
    from chipbench import run

    spec = chip_smoke.DECODER_REFERENCES["xing4_29b_a4b"]
    _manifest, _chips, cell, cfg, cfgmod = run.load_cell(spec["cell"])
    cfg.update(hidden_size=32, intermediate_size=48, moe_intermediate_size=16,
               kv_lora_rank=12, q_lora_rank=20, qk_nope_head_dim=16, qk_rope_head_dim=8,
               v_head_dim=16, num_attention_heads=2, n_routed_experts=2, router_experts=8,
               ep_rank=1, num_experts_per_tok=2, vocab_size=64, dtype="float32")
    cell.update(batch=2, seq_len=32)
    out = chip_smoke.decoder_reference_numbers(cfg, cell, cfgmod, block=8,
                                               config="xing4_29b_a4b")
    assert out["tokens"] == 32 and out["logits_rms"] > 0
    assert out["logits_err"]["max"] < 1e-4 and out["mtp_logits_err"]["max"] < 1e-4
    assert out["fp8_control_logits_err"]["rms"] > 30 * out["logits_err"]["rms"]
    assert abs(out["loss_system"] - out["loss_reference_f32"]) < 1e-4
    assert out["loss_reference_f32_own_picks"] > out["loss_reference_f32"]   # + 0.1 CE_mtp
    assert sorted(out["gradients"]) == sorted(n for n, *_ in spec["grads"])
    for name, g in out["gradients"].items():
        assert g["rel_l2"] < 3e-3 and g["cosine"] > 0.9999, (name, g)
