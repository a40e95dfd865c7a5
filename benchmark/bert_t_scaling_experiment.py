"""Why does BERT MFU sag from 35.6% (T=128) to 26.9% (T=512)?

Round-4 verdict #1: the 9-point drop is batch-invariant and was "the
next lever to profile, not yet explained".  This experiment explains it
with the mfu_residuals methodology: every comparison is a PAIR of
compiled programs interleaved in ONE process window (drift cancels),
one subprocess per pair so an OOM in one can't poison the rest.

Pairs (all dense attention, B·T = 4096 tokens/step):

  sag        base128  vs base512        the effect itself, same-window
  drop512    base512  vs nodrop512      attention-dropout RNG+mask cost
  drop128    base128  vs nodrop128      (scales with B·H·T² = tokens·H·T,
                                        so its per-token cost grows with T)
  attn512    base512  vs noattn512      attention-mix excised: q/k/v/proj
  attn128    base128  vs noattn128      matmuls kept (damped by 1e-30 so
                                        XLA can't DCE them), score/softmax/
                                        dropout/context removed
  head512    base512  vs bf16head512    MLM log-softmax: f32 upcast vs
  head128    base128  vs bf16head128    bf16 with f32-accumulated sum

Each pair reports per-round tokens/s for both variants and the median
same-round ratio.  Attribution logic: if excising X closes the sag by
the same number of points at T=512 but not T=128, X is the T-scaling
cost.  Results: `results/bert_t_scaling_tpu_v5e.json`, discussion in
BERT_ANALYSIS.md (round-5 section).
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

import numpy as onp

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

# This experiment ATTRIBUTES the round-4 numbers, whose dropout masks
# were threefry; production now defaults to the hardware RNG (the change
# this experiment motivated, ops/nn.py:_dropout_key).  Pin the old
# default so base*/nodrop* still measure what the analysis describes and
# the rbg pairs stay threefry-vs-rbg comparisons.
os.environ["MXNET_DROPOUT_RNG"] = "threefry"

L, U, V = 12, 768, 30522
WARMUP = 5
ITERS = 25
ROUNDS = 3

CONFIGS = {
    # name: (B, T, dropout, surgery)
    "base128": (32, 128, 0.1, None),
    "base512": (8, 512, 0.1, None),
    "nodrop128": (32, 128, 0.0, None),
    "nodrop512": (8, 512, 0.0, None),
    "noattn128": (32, 128, 0.1, "noattn"),
    "noattn512": (8, 512, 0.1, "noattn"),
    "bf16head128": (32, 128, 0.1, "bf16head"),
    "bf16head512": (8, 512, 0.1, "bf16head"),
    "rbgdrop128": (32, 128, 0.1, "rbgdrop"),
    "rbgdrop512": (8, 512, 0.1, "rbgdrop"),
}

PAIRS = {
    "sag": ("base128", "base512"),
    # the decisive pair: if the sag vanishes without dropout, the whole
    # T-scaling cost IS the attention-dropout chain
    "sag_nodrop": ("nodrop128", "nodrop512"),
    "drop512": ("base512", "nodrop512"),
    "drop128": ("base128", "nodrop128"),
    "attn512": ("base512", "noattn512"),
    "attn128": ("base128", "noattn128"),
    "head512": ("base512", "bf16head512"),
    "head128": ("base128", "bf16head128"),
    # same Bernoulli semantics, hardware RNG stream: isolates "threefry
    # bits are expensive" from "the dropout chain breaks XLA fusion"
    "rbg512": ("base512", "rbgdrop512"),
    "rbg128": ("base128", "rbgdrop128"),
}


def _flops_per_token(n_dense, t, with_attention=True):
    return 6.0 * n_dense + (12.0 * L * U * t if with_attention else 0.0)


def _build_step(name):
    import mxnet_tpu as mx
    from mxnet_tpu.gluon import FusedTrainStep, Trainer
    from mxnet_tpu.gluon.block import HybridBlock
    from mxnet_tpu.models import BertForPretraining
    from mxnet_tpu.models import transformer as tr

    b, t, drop, surgery = CONFIGS[name]

    if surgery == "rbgdrop":
        # force the hardware-RNG key re-wrap (the production
        # ops.nn._dropout_key with impl pinned), regardless of the
        # threefry baseline env this process runs under
        from mxnet_tpu.ops import nn as _nnops
        _orig_dropout = _nnops.dropout

        def rbg_dropout(data, key, p=0.5, axes=None, mode="training"):
            if p == 0.0 or mode != "training":
                return data
            return _orig_dropout(data, _nnops._dropout_key(key, impl="rbg"),
                                 p=p, axes=axes, mode=mode)
        _nnops.dropout = rbg_dropout

    if surgery == "noattn":
        # keep all four dense projections live (1e-30 damping defeats the
        # algebraic simplifier without letting q/k affect the result),
        # drop the score/softmax/attn-dropout/context chain — the only
        # parts whose cost scales with T at fixed B·T
        def noattn_forward(self, x, mask=None):
            q = self.query(x)
            k = self.key(x)
            v = self.value(x)
            return self.proj(v + (q + k) * 1e-30)
        tr.MultiHeadAttention.forward = noattn_forward

    model = BertForPretraining(vocab_size=V, units=U, hidden_size=3072,
                               num_layers=L, num_heads=12,
                               max_length=512, dropout=drop,
                               use_flash=False)
    model.initialize()
    model.cast("bfloat16")

    bf16_head = surgery == "bf16head"

    class PretrainLoss(HybridBlock):
        def __init__(self, m):
            super().__init__()
            self.m = m

        def forward(self, tokens, segments, labels):
            mlm_logits, nsp_logits = self.m(tokens, segments)
            if bf16_head:
                # bf16 shift/exp with f32-accumulated sum: skips the
                # 2·(B·T·V) f32 materialisation (~1 GB/step at T=512)
                s = mlm_logits - mx.np.max(mlm_logits, axis=-1,
                                           keepdims=True)
                lse = mx.np.log(mx.np.sum(mx.np.exp(s), axis=-1,
                                          keepdims=True,
                                          dtype="float32"))
                logp = s.astype("float32") - lse
            else:
                logp = mx.npx.log_softmax(
                    mlm_logits.astype("float32"), axis=-1)
            mlm = -mx.np.mean(mx.npx.pick(logp, labels, axis=-1))
            nsp = -mx.np.mean(
                mx.npx.log_softmax(nsp_logits.astype("float32"))[:, 0])
            return mlm + nsp

    mod = PretrainLoss(model)
    tokens = mx.np.array(onp.random.randint(0, V, (b, t)), dtype="int32")
    segments = mx.np.array(onp.zeros((b, t)), dtype="int32")
    labels = mx.np.array(onp.random.randint(0, V, (b, t)), dtype="int32")
    trainer = Trainer(model.collect_params(), "adam",
                      {"learning_rate": 1e-4})
    step = FusedTrainStep(mod, trainer)

    for _ in range(WARMUP):
        step(tokens, segments, labels, batch_size=b)
    mx.waitall()

    params = model.collect_params()
    n_total = sum(int(onp.prod(p.shape)) for p in params.values())
    n_embed = sum(int(onp.prod(p.shape)) for pn, p in params.items()
                  if "embed" in pn.lower())
    n_dense = n_total - n_embed + U * V
    assert n_total > 100e6

    def run_window():
        t0 = time.perf_counter()
        for _ in range(ITERS):
            step(tokens, segments, labels, batch_size=b)
        import mxnet_tpu as _mx
        _mx.waitall()
        return b * t * ITERS / (time.perf_counter() - t0)

    return run_window, n_dense, b, t


def run_pair(pair):
    a_name, b_name = PAIRS[pair]
    run_a, nd_a, ba, ta = _build_step(a_name)
    # surgery monkeypatches are process-global; a pair never mixes two
    # different surgeries (base is always the A side), but B must build
    # AFTER A so a surgery B-side patch doesn't leak into A's trace
    run_b, nd_b, bb, tb = _build_step(b_name)

    rows = []
    ratios = []
    for r in range(ROUNDS):
        tok_a = run_a()
        tok_b = run_b()
        ratios.append(tok_b / tok_a)
        rows.append({"round": r, a_name: round(tok_a), b_name: round(tok_b)})
    ratios.sort()
    med = ratios[len(ratios) // 2]

    import jax
    from mxnet_tpu.analysis.census import PEAKS, peaks_key
    peak = PEAKS[peaks_key(jax.devices()[0].device_kind)]["flops"]

    def mfu(tok, nd, t, attn=True):
        return round(tok * _flops_per_token(nd, t, attn) / peak, 4)

    out = {
        "experiment": f"bert_t_scaling:{pair}",
        "pair": [a_name, b_name],
        "rounds": rows,
        "median_ratio_b_over_a": round(med, 4),
        "mfu_a": mfu(max(r[a_name] for r in rows), nd_a, ta),
        "mfu_b": mfu(max(r[b_name] for r in rows), nd_b, tb,
                     attn=not b_name.startswith("noattn")),
    }
    print(json.dumps(out), flush=True)
    return out


def run_census():
    """Compiled-program census of the isolated dense-attention subgraph
    (exactly MultiHeadAttention's einsum path) fwd+bwd, with and without
    attention dropout, at T=128 and T=512: XLA cost_analysis flops /
    bytes accessed + transcendental count.  Distinguishes 'threefry bits
    are expensive' (flops/transcendentals jump) from 'dropout breaks
    fusion' (bytes jump)."""
    import jax
    import jax.numpy as jnp

    h, d = 12, 64
    out = {"experiment": "bert_t_scaling:census", "rows": []}
    for (b, t) in ((32, 128), (8, 512)):
        for drop in (0.0, 0.1):
            def attn_loss(q, k, v, key):
                s = jnp.einsum("bthd,bshd->bhts", q, k) / (d ** 0.5)
                a = jax.nn.softmax(s, axis=-1)
                if drop:
                    m = jax.random.bernoulli(key, 1 - drop, a.shape)
                    a = jnp.where(m, a / (1 - drop), 0).astype(a.dtype)
                o = jnp.einsum("bhts,bshd->bthd", a, v)
                return (o.astype(jnp.float32) ** 2).sum()

            g = jax.jit(jax.grad(attn_loss, argnums=(0, 1, 2)))
            args_ = [jnp.ones((b, t, h, d), jnp.bfloat16)] * 3 + [
                jax.random.key(0)]
            from mxnet_tpu.analysis import compiled_cost_summary
            cs = compiled_cost_summary(g.lower(*args_).compile())
            out["rows"].append({"batch": b, "seq": t, "dropout": drop, **cs})
    print(json.dumps(out), flush=True)
    return out


def main():
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--pair", default=None, choices=sorted(PAIRS))
    p.add_argument("--pairs", default=None,
                   help="comma-separated subset to run (default: all)")
    p.add_argument("--census", action="store_true")
    p.add_argument("--output", default=None)
    args = p.parse_args()

    if args.census:
        row = run_census()
        if args.output:
            merged = [row]
            if os.path.exists(args.output):
                old = json.load(open(args.output))
                merged = [r for r in old
                          if r["experiment"] != row["experiment"]] + [row]
            with open(args.output, "w") as f:
                json.dump(merged, f, indent=1)
        return
    if args.pair:
        run_pair(args.pair)
        return

    rows = []
    wanted = args.pairs.split(",") if args.pairs else list(PAIRS)
    for pair in wanted:
        for attempt in range(2):
            res = subprocess.run(
                [sys.executable, os.path.abspath(__file__), "--pair", pair],
                capture_output=True, text=True, timeout=2400)
            lines = [ln for ln in res.stdout.splitlines()
                     if ln.startswith("{")]
            if lines:
                rows.append(json.loads(lines[-1]))
                break
            err = (res.stderr or "")[-400:]
            err_row = {"experiment": f"bert_t_scaling:{pair}",
                       "error": err}
            print(json.dumps(err_row), flush=True)
            if "UNAVAILABLE" in err and attempt == 0:
                time.sleep(90)   # shared worker restart
                continue
            # a failed re-run must not leave the pair's STALE row in the
            # artifact looking fresh — the error row replaces it
            rows.append(err_row)
            break
    if args.output:
        merged = rows
        if os.path.exists(args.output):
            # merge with prior pairs: latest run of a pair wins
            old = json.load(open(args.output))
            have = {r["experiment"] for r in rows}
            merged = [r for r in old if r["experiment"] not in have] + rows
        with open(args.output, "w") as f:
            json.dump(merged, f, indent=1)


if __name__ == "__main__":
    main()
