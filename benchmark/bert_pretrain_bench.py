"""BERT-base pretraining throughput + MFU on one chip (BASELINE config 4).

MLM+NSP loss over the Gluon BERT, bf16, batch 32 x seq 128, driven by
`gluon.FusedTrainStep` (one XLA program per step).  Prints one JSON line
(best of three fully-drained windows) carrying tokens/s AND
model-FLOPs-utilization against the bf16 peak of the device it ran on
(`analysis/census.PEAKS`), so the transformer perf story is judged the
same way the ResNet one is (MFU_ANALYSIS.md / BERT_ANALYSIS.md).

The measured configuration is RECIPE-REALISTIC (round 6): padded
variable-length batches (ragged valid lengths, MLPerf-BERT-style) with
the padding mask threaded through attention, and attention dropout 0.1
— the configuration MLPerf-style BERT actually trains under.  The flash
tier runs both in-kernel, so long-T runs stay on the fast path instead
of silently falling back to the dense O(T^2) softmax (``--unmasked``
restores the old idealized A/B configuration).

MFU accounting: training FLOPs/token = 6·N_dense (fwd+bwd weight
matmuls; N_dense excludes embedding tables, whose forward is a gather)
+ 12·L·U·T attention-score/context FLOPs.  The MLM head's vocab
projection (tied embedding, U×V matmul) IS dense compute and dominates
at T=128 — it is counted in N_dense.  Tokens/s counts B·T slots (padded
included) so numbers stay comparable across rounds; the JSON also
carries the mean valid occupancy.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as onp

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

B, T = 32, 128
L, U, V = 12, 768, 30522
WARMUP = 6
ITERS = 30


def flops_per_token(n_dense, t):
    # 6 FLOPs per dense weight per token (2 fwd + 4 bwd) + attention
    # scores/context: 2 matmuls of 2·t·U each, fwd+bwd -> 12·t·U per
    # layer per token
    return 6.0 * n_dense + 12.0 * L * U * t


def build(batch, seq, use_flash="auto", remat=False, unmasked=False, dp=0):
    """The pretraining step this bench times, as (step, batch, model):
    ``step(*batch, batch_size=batch)`` is one `FusedTrainStep` of
    BERT-base MLM+NSP in bf16 under Adam.  `chip_smoke.py` takes its BERT
    phase from here, so the smoke runs what the bench measures."""
    import mxnet_tpu as mx
    from mxnet_tpu.gluon import FusedTrainStep, Trainer
    from mxnet_tpu.gluon.block import HybridBlock
    from mxnet_tpu.models import BertForPretraining

    B, T = batch, seq
    # the recipe-realistic headline keeps the reference's dropout=0.1 at
    # EVERY T — the flash tier applies attention dropout (and the padding
    # mask) in-kernel, so long-T no longer needs a dropout-free carve-out
    drop = 0.0 if unmasked else 0.1
    model = BertForPretraining(vocab_size=V, units=U, hidden_size=3072,
                               num_layers=L, num_heads=12,
                               max_length=max(512, T), dropout=drop,
                               use_flash=use_flash, remat=remat)
    model.initialize()
    model.cast("bfloat16")

    class PretrainLoss(HybridBlock):
        def __init__(self, m):
            super().__init__()
            self.m = m

        def forward(self, tokens, segments, labels, valid_mask=None):
            mlm_logits, nsp_logits = self.m(tokens, segments, valid_mask)
            logp = mx.npx.log_softmax(mlm_logits.astype("float32"), axis=-1)
            picked = mx.npx.pick(logp, labels, axis=-1)
            if valid_mask is None:
                mlm = -mx.np.mean(picked)
            else:
                # padded positions carry no loss (MLPerf-style accounting)
                m = valid_mask.astype("float32")
                mlm = -(picked * m).sum() / m.sum()
            nsp = -mx.np.mean(
                mx.npx.log_softmax(nsp_logits.astype("float32"))[:, 0])
            return mlm + nsp

    mod = PretrainLoss(model)
    tokens = mx.np.array(onp.random.randint(0, V, (B, T)), dtype="int32")
    segments = mx.np.array(onp.zeros((B, T)), dtype="int32")
    labels = mx.np.array(onp.random.randint(0, V, (B, T)), dtype="int32")
    if unmasked:
        arrays = (tokens, segments, labels)
    else:
        # ragged MLPerf-style padding: valid prefixes in [T/2, T]
        lens = onp.random.RandomState(11).randint(T // 2, T + 1, size=B)
        mask_np = (onp.arange(T)[None, :] < lens[:, None])
        arrays = (tokens, segments, labels,
                  mx.np.array(mask_np.astype(onp.int32), dtype="int32"))
    trainer = Trainer(model.collect_params(), "adam", {"learning_rate": 1e-4})
    mesh = None
    if dp:
        from mxnet_tpu.parallel import mesh as pmesh
        mesh = pmesh.make_mesh({"dp": dp})
    return FusedTrainStep(mod, trainer, mesh=mesh), arrays, model


def main():
    global B, T
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--output", default=None)
    p.add_argument("--batch", type=int, default=B)
    p.add_argument("--seq", type=int, default=T)
    p.add_argument("--dp", type=int, default=0,
                   help="data-parallel mesh size (multi-host runs)")
    p.add_argument("--use-flash", default="auto",
                   choices=("auto", "true", "false"),
                   help="auto (measured crossovers) | true | false")
    p.add_argument("--remat", action="store_true",
                   help="rematerialization boundary around each encoder "
                        "layer (npx.remat): backward recomputes "
                        "activations, memory O(layers) -> O(1)")
    p.add_argument("--unmasked", action="store_true",
                   help="idealized A/B configuration: full-length batches, "
                        "no padding mask, no attention dropout (the pre-"
                        "round-6 setup)")
    args = p.parse_args()
    B, T = args.batch, args.seq

    import mxnet_tpu as mx

    use_flash = {"auto": "auto", "true": True, "false": False}[args.use_flash]
    drop = 0.0 if args.unmasked else 0.1
    step, batch, model = build(B, T, use_flash=use_flash, remat=args.remat,
                               unmasked=args.unmasked, dp=args.dp)
    occupancy = 1.0 if args.unmasked else \
        float((batch[3].asnumpy() != 0).mean())

    for _ in range(WARMUP):
        loss = step(*batch, batch_size=B)
    loss.wait_to_read()
    mx.waitall()

    # size the window from a measured step so it dwarfs the closing
    # drain
    from timing_util import measured_step_s, window_iters
    global ITERS
    ITERS = window_iters(measured_step_s(
        lambda: step(*batch, batch_size=B), mx.waitall))

    # dense-param count for MFU: everything except the embedding tables
    # (their forward is a gather, not a matmul; the TIED mlm vocab
    # projection is a real U x V matmul and is added back explicitly).
    # Counted AFTER warmup: deferred shape inference leaves ~75 dense
    # params shapeless until the first forward materialises them.
    params = model.collect_params()
    n_total = sum(int(onp.prod(p.shape)) for p in params.values())
    n_embed = sum(int(onp.prod(p.shape)) for name, p in params.items()
                  if "embed" in name.lower())
    n_dense = n_total - n_embed + U * V  # + tied vocab projection matmul
    assert n_total > 100e6, f"param shapes not materialised: {n_total}"

    windows = []
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(ITERS):
            step(*batch, batch_size=B)
        mx.waitall()
        windows.append(B * T * ITERS / (time.perf_counter() - t0))

    tok_s = max(windows)
    fpt = flops_per_token(n_dense, T)
    import jax
    from mxnet_tpu.analysis.census import PEAKS, peaks_key
    kind = jax.devices()[0].device_kind
    n_chips = max(args.dp, 1)  # tok_s is the global rate on a dp mesh
    result = {
        "metric": "bert_base_pretrain_bf16_tokens_per_s",
        "value": round(tok_s, 0),
        "unit": "tokens/s",
        "use_flash": args.use_flash,
        "remat": args.remat,
        "dropout": drop,
        "masked": not args.unmasked,
        "valid_occupancy": round(occupancy, 4),
        "batch": B, "seq_len": T,
        "window_tokens_per_s": [round(w) for w in windows],
        "params_total": n_total,
        "params_dense_for_mfu": int(n_dense),
        "flops_per_token": round(fpt),
        "n_chips": n_chips,
        "model_tflops_per_s": round(tok_s * fpt / 1e12, 2),
        "device_kind": kind,
        "mfu_bf16": round(
            tok_s * fpt / (PEAKS[peaks_key(kind)]["flops"] * n_chips), 4),
    }
    line = json.dumps(result)
    print(line)
    if args.output:
        with open(args.output, "w") as f:
            f.write(line + "\n")


if __name__ == "__main__":
    main()
