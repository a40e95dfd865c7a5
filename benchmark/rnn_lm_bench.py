"""BASELINE config 5: the LSTM word language model on one chip.

The reference ships a fused RNN kernel as a *performance* feature
(`/root/reference/src/operator/rnn.cc:295`, cuDNN dispatch at
`rnn-inl.h:421`); here the LSTM lowers to `lax.scan` with the input
projection batched OUTSIDE the scan (one MXU matmul over all T,
`gluon/rnn/rnn_layer.py:_run_single_direction`), so the sequential part
is only the h→h recurrence.  This bench measures the classic
example/rnn "medium" word-LM shape — emb 650, 2×LSTM(650), tied-free
vocab head, bptt 35 — train step via FusedTrainStep, bf16, drained
windows.

Where scan-RNN lands vs the roofline (committed chip numbers:
``results/rnn_lm_tpu_v5e.json``; discussion in BERT_ANALYSIS.md
"Config 5" section):

- per-token train FLOPs = 3·2·[Σ_l 4H(in_l+H) + H·V] (3 = fwd + 2×bwd)
- the h→h matmul (B, H)x(H, 4H) inside the scan serializes over T
  steps/layer: at B=32, H=650 that is a 108-MFLOP matmul per step —
  big enough to keep the MXU busy, but every step pays the scan
  iteration latency, which is why tokens/s grows with batch.

Usage: python benchmark/rnn_lm_bench.py [--batch 32] [--bptt 35]
       [--output FILE]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as onp

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

V, E, H, L = 10000, 650, 650, 2     # example/rnn "medium" (PTB vocab)
WARMUP = 5


def flops_per_token():
    per_layer = [8.0 * H * (E + H), 8.0 * H * (H + H)]  # 2·4H·(in+H)
    fwd = sum(per_layer) + 2.0 * H * V                  # + vocab head
    return 3.0 * fwd                                    # fwd + bwd


def main():
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--batch", type=int, default=32)
    p.add_argument("--bptt", type=int, default=35)
    p.add_argument("--dtype", default="bfloat16")
    p.add_argument("--output", default=None)
    p.add_argument("--pre-tune", type=float, default=None,
                   help="pre-autotune tokens/s baseline for this config; "
                        "records pre_tune_tokens_per_s + speedup_vs_pre_"
                        "tune in the artifact (PR 18 acceptance: b=32 "
                        ">= 1.5x)")
    args = p.parse_args()
    b, t = args.batch, args.bptt

    import mxnet_tpu as mx
    from mxnet_tpu.gluon import FusedTrainStep, Trainer, nn, rnn
    from mxnet_tpu.gluon.block import HybridBlock

    class WordLM(HybridBlock):
        def __init__(self):
            super().__init__()
            self.embed = nn.Embedding(V, E)
            self.lstm = rnn.LSTM(H, num_layers=L, layout="TNC",
                                 input_size=E)
            self.decoder = nn.Dense(V, flatten=False)

        def forward(self, data):          # (T, N) int tokens
            x = self.embed(data)
            out = self.lstm(x)
            return self.decoder(out)      # (T, N, V)

    class LMLoss(HybridBlock):
        def __init__(self, m):
            super().__init__()
            self.m = m

        def forward(self, data, target):
            logits = self.m(data)
            logp = mx.npx.log_softmax(logits.astype("float32"), axis=-1)
            return -mx.np.mean(mx.npx.pick(logp, target, axis=-1))

    model = WordLM()
    model.initialize()
    if args.dtype != "float32":
        model.cast(args.dtype)
    mod = LMLoss(model)
    data = mx.np.array(onp.random.randint(0, V, (t, b)), dtype="int32")
    target = mx.np.array(onp.random.randint(0, V, (t, b)), dtype="int32")
    trainer = Trainer(model.collect_params(), "sgd",
                      {"learning_rate": 1.0, "momentum": 0.9})
    step = FusedTrainStep(mod, trainer)

    for _ in range(WARMUP):
        loss = step(data, target, batch_size=b)
    loss.wait_to_read()
    mx.waitall()

    # drain-aware window sizing (shared): at b=32 a step is ~4 ms, and a
    # short window counts the closing drain as compute
    from timing_util import measured_step_s, window_iters
    iters = window_iters(measured_step_s(
        lambda: step(data, target, batch_size=b), mx.waitall))

    windows = []
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(iters):
            step(data, target, batch_size=b)
        mx.waitall()
        windows.append(b * t * iters / (time.perf_counter() - t0))

    tok_s = max(windows)
    fpt = flops_per_token()
    import jax
    from mxnet_tpu.analysis.census import PEAKS, peaks_key
    kind = jax.devices()[0].device_kind
    result = {
        "metric": "lstm_word_lm_tokens_per_s",
        "value": round(tok_s),
        "unit": "tokens/s",
        "dtype": args.dtype,
        "batch": b, "bptt": t,
        "vocab": V, "emb": E, "hidden": H, "layers": L,
        "window_tokens_per_s": [round(w) for w in windows],
        "flops_per_token": round(fpt),
        "model_tflops_per_s": round(tok_s * fpt / 1e12, 2),
        "device_kind": kind,
        "mfu_bf16": round(tok_s * fpt / PEAKS[peaks_key(kind)]["flops"], 4),
        "steps_per_s": round(tok_s / (b * t), 2),
    }
    if args.pre_tune:
        result["pre_tune_tokens_per_s"] = round(args.pre_tune)
        result["speedup_vs_pre_tune"] = round(tok_s / args.pre_tune, 4)
    line = json.dumps(result)
    print(line, flush=True)
    if args.output:
        with open(args.output, "a") as f:
            f.write(line + "\n")


if __name__ == "__main__":
    main()
