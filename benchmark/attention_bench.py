"""Long-sequence attention: flash (Pallas) vs dense (XLA) on one chip.

The long-context story's perf evidence: where does the blockwise Pallas
kernel (memory O(T * block)) overtake XLA's dense fusion (materialized
(T, T) scores)?  Timed as device-side `lax.scan` loops — the opperf
treatment — because a short dispatch loop buries a sub-10 ms kernel
under its host round trips.  Each scan iteration
chains the output back into q with a 1e-24 perturbation so nothing is
hoisted or dead-coded; the drain cost is measured separately and
subtracted.  Prints one JSON line per (T, variant, direction) with a
`reliable` flag (scan work >= 2x drain).
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

import numpy as onp

sys.path.insert(0, os.path.dirname(__file__))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from timing_util import scan_ms  # noqa: E402

B, H, D = 4, 8, 64


def main():
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--output", default=None,
                   help="write all result lines as a JSON array here")
    p.add_argument("--seq-lens", default="512,1024,2048,4096,8192",
                   help="comma-separated sequence lengths")
    p.add_argument("--kinds", default="fwd,fwd_bwd",
                   help="comma-separated subset of fwd,fwd_bwd")
    p.add_argument("--causal", action="store_true",
                   help="causal variants: dense applies a tril mask, flash "
                        "skips fully-masked blocks (metric gains '_causal')")
    p.add_argument("--masked", action="store_true",
                   help="key-padding variants (metric gains '_masked'): "
                        "ragged per-batch valid lengths (~75%% mean "
                        "occupancy, MLPerf-BERT-style); dense applies the "
                        "mask via where(), flash runs it in-kernel and "
                        "skips/declamps fully-padded tail blocks")
    p.add_argument("--dropout", type=float, default=0.0,
                   help="attention-dropout rate (metric gains '_dropN'): "
                        "flash draws in-kernel threefry bits; dense pays "
                        "an explicit (B,H,T,T) bernoulli mask like the "
                        "production dense path does")
    p.add_argument("--block-sweep", default=None,
                   help="comma-separated bqXbk pairs (e.g. "
                        "'512x512,512x1024,256x1024') to re-pick flash "
                        "block sizes for the masked/dropout variants; "
                        "each adds a flash row tagged with the blocks")
    args = p.parse_args()

    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops import pallas_kernels as pk

    causal = args.causal
    drop = args.dropout
    key = jax.random.key(7)

    def lengths_for(t):
        # ragged MLPerf-style padding: valid prefixes in [t/2, t]
        rng = onp.random.RandomState(11)
        return rng.randint(t // 2, t + 1, size=B)

    def mask_for(t):
        if not args.masked:
            return None
        lens = lengths_for(t)
        return jnp.asarray(onp.arange(t)[None, :] < lens[:, None],
                           jnp.int32)

    def dense(q, k, v, mask=None):
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * (D ** -0.5)
        t = s.shape[-1]
        if causal:
            cm = jnp.tril(jnp.ones((t, t), bool))
            s = jnp.where(cm, s, -1e30)
        if mask is not None:
            s = jnp.where(mask[:, None, None, :] != 0, s, -1e30)
        p = jax.nn.softmax(s.astype(jnp.float32), axis=-1)
        if drop:
            keep = jax.random.bernoulli(key, 1.0 - drop, p.shape)
            p = jnp.where(keep, p / (1.0 - drop), 0.0)
        p = p.astype(q.dtype)
        return jnp.einsum("bhqk,bhkd->bhqd", p, v)

    def make_flash(bq=None, bk=None):
        def flash(q, k, v, mask=None):
            return pk.flash_attention(q, k, v, causal=causal, mask=mask,
                                      dropout=drop,
                                      key=key if drop else None,
                                      block_q=bq, block_k=bk)
        return flash

    suffix = ("_causal" if causal else "") + \
        ("_masked" if args.masked else "") + \
        (f"_drop{int(drop * 100)}" if drop else "")
    impls = [("dense", dense), ("flash", make_flash())]
    if args.block_sweep:
        for pair in args.block_sweep.split(","):
            bq, bk = (int(x) for x in pair.lower().split("x"))
            impls.append((f"flash_bq{bq}_bk{bk}", make_flash(bq, bk)))

    rows = []
    for t in (int(x) for x in args.seq_lens.split(",")):
        qkv = [jnp.asarray(onp.random.randn(B, H, t, D), jnp.bfloat16)
               for _ in range(3)]
        mask_t = mask_for(t)
        for kind, grad in (("fwd", False), ("fwd_bwd", True)):
            if kind not in args.kinds.split(","):
                continue
            for name, base in impls:
                impl = (base if mask_t is None else
                        functools.partial(base, mask=mask_t))
                tag = f"{name}_{kind}{suffix}"
                try:
                    # full dq/dk/dv backward, not just dq (grad="all")
                    ms, n, ok = scan_ms(impl, qkv,
                                        grad="all" if grad else False)
                    row = {
                        "metric": f"attn_{tag}_ms",
                        "seq_len": t, "value": round(ms, 3), "unit": "ms",
                        "tokens_per_s": round(B * t / (ms / 1e3)),
                        "scan_len": n, "reliable": ok,
                    }
                except Exception as e:
                    row = {"metric": f"attn_{tag}_error",
                           "seq_len": t, "error": str(e)[:120]}
                    if "UNAVAILABLE" in str(e):
                        # the shared worker crashed; give it time to
                        # restart so later combos aren't poisoned
                        time.sleep(90)
                print(json.dumps(row), flush=True)
                rows.append(row)
    if args.output:
        with open(args.output, "w") as f:
            json.dump(rows, f, indent=1)


if __name__ == "__main__":
    main()
