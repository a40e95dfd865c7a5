"""Bucketed vs per-key gradient allreduce microbenchmark (ISSUE 4).

Sweeps tensor-count x size-distribution x bucket-bytes over the 8-device
virtual mesh (the same dryrun substrate as `__graft_entry__`), per-key vs
bucketed, across {dense, 2bit, int8, fp8} compression modes, and prints
one JSON line per config plus a summary speedup table.  Verdict:
`benchmark/COLLECTIVES_ANALYSIS.md`.

The headline distribution is ResNet-50-like: 160 gradient tensors whose
median is 256 floats (1 KB — BN gamma/beta and biases), with a small
number of wide conv/fc weights carrying most of the bytes.  Per-key,
every one of those 160 tensors pays an XLA program launch; bucketed they
collapse to a handful of packed psums.

Usage::

    python benchmark/allreduce_bench.py            # full sweep
    python benchmark/allreduce_bench.py --iters 20 --dists resnet50
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

# the sweep must own the virtual mesh BEFORE jax initializes (same dance
# as tests/conftest.py and __graft_entry__._acquire_devices)
os.environ.setdefault("JAX_PLATFORMS", "cpu")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import numpy as onp  # noqa: E402

N_COPIES = 8

# -- size distributions ------------------------------------------------------
# resnet50: the ResNet-50 tensor-count/median profile — 160 tensors,
# median 256 floats (1 KB: the BN gamma/beta + bias tail that makes
# per-key dispatch latency-bound) — at 1/16 channel width, so the
# virtual-mesh run measures the LAUNCH-bound regime this optimization
# targets rather than the CPU backend's memcpy bandwidth.  resnet50_full
# keeps the full-width byte volume (~56 MB) to expose the byte-bound
# regime, where bucketing is decided by the wire, not the launch count.
DISTRIBUTIONS = {
    "resnet50": [256] * 104 + [1024] * 26 + [16384] * 22 + [65536] * 8,
    "resnet50_full": (
        [256] * 104 + [16384] * 26 + [262144] * 22 + [1048576] * 8),
    "tiny64": [1024] * 64,           # uniformly tiny: pure launch latency
    "wide16": [1 << 20] * 16,        # uniformly wide: wire/compute bound
}


def build_pairs(sizes, seed=0):
    import mxnet_tpu as mx

    rs = onp.random.RandomState(seed)
    pairs = []
    for k, size in enumerate(sizes):
        base = rs.randn(size).astype(onp.float32)
        pairs.append((k, [
            mx.np.array(base + c, ctx=mx.cpu(c)) for c in range(N_COPIES)
        ]))
    return pairs


def make_store(mode, bucket_bytes=None):
    from mxnet_tpu import kvstore
    from mxnet_tpu.kvstore.bucketing import GradBucketer

    kv = kvstore.create("tpu_ici")
    if mode == "2bit":
        kv.set_gradient_compression({"type": "2bit", "threshold": 0.5})
    elif mode != "dense":
        kv.set_gradient_compression({"type": mode})
    if bucket_bytes is not None:
        kv._bucketer = GradBucketer(bucket_bytes=bucket_bytes)
    return kv


def run_config(dist, impl, mode, iters, warmup):
    """One (distribution, implementation, mode) config; returns the JSON
    row.  ``impl`` is "perkey" or a bucket-bytes int; ``mode`` is dense,
    2bit, int8, or fp8."""
    import mxnet_tpu as mx
    from mxnet_tpu import telemetry

    sizes = DISTRIBUTIONS[dist]
    pairs = build_pairs(sizes)
    issue = list(reversed(pairs))  # the Trainer's reverse-registration order
    bucketed = impl != "perkey"
    kv = make_store(mode, bucket_bytes=impl if bucketed else None)

    def step():
        if bucketed:
            kv.pushpull_list(issue)
        else:
            for k, vals in issue:
                kv.pushpull(k, vals)

    for _ in range(warmup):
        step()
    mx.waitall()

    reg = telemetry.default_registry()
    name = "mxtpu_kvstore_collective_launches_total"
    before = reg.get_sample_value(name) or 0.0
    t0 = time.perf_counter()
    for _ in range(iters):
        step()
    mx.waitall()
    dt = (time.perf_counter() - t0) / iters
    launches = ((reg.get_sample_value(name) or 0.0) - before) / iters

    grad_mb = sum(sizes) * 4 / 2 ** 20
    return {
        "dist": dist,
        "n_tensors": len(sizes),
        "median_kb": round(
            float(onp.median(onp.asarray(sizes))) * 4 / 1024, 2),
        "grad_mb": round(grad_mb, 2),
        "n_copies": N_COPIES,
        "impl": "perkey" if not bucketed else f"bucketed_{impl >> 20}mb",
        "mode": mode,
        "ms_per_step": round(dt * 1e3, 3),
        "grad_mb_per_s": round(grad_mb / dt, 1),
        "launches_per_step": round(launches, 1),
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--warmup", type=int, default=3)
    ap.add_argument("--dists", nargs="*", default=list(DISTRIBUTIONS))
    ap.add_argument("--bucket-bytes", nargs="*", type=int,
                    default=[1 << 20, 4 << 20, 16 << 20])
    ap.add_argument("--modes", nargs="*",
                    default=["dense", "2bit", "int8", "fp8"])
    args = ap.parse_args()

    rows = []
    for dist in args.dists:
        for mode in args.modes:
            for impl in ["perkey"] + args.bucket_bytes:
                row = run_config(dist, impl, mode, args.iters, args.warmup)
                rows.append(row)
                print(json.dumps(row), flush=True)

    # verdict lines: best bucketed config vs per-key, per (dist, mode)
    for dist in args.dists:
        for mode in args.modes:
            perkey = next(r for r in rows if r["dist"] == dist
                          and r["mode"] == mode and r["impl"] == "perkey")
            best = min((r for r in rows if r["dist"] == dist
                        and r["mode"] == mode and r["impl"] != "perkey"),
                       key=lambda r: r["ms_per_step"])
            print(json.dumps({
                "verdict": f"{dist}/{mode}",
                "speedup": round(perkey["ms_per_step"] /
                                 best["ms_per_step"], 2),
                "best_impl": best["impl"],
                "launches": f"{perkey['launches_per_step']:.0f} -> "
                            f"{best['launches_per_step']:.0f}",
            }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
