"""Experiment: Pallas fused matmul + BN-stats epilogue vs XLA unfused.

MFU_ANALYSIS.md "what would move it" #1: the BN training stats (per-channel
sum / sum-of-squares) re-read the conv output from HBM after XLA's conv
kernel has written it.  For the 1x1 convolutions — more than half of
ResNet-50's layers, and exactly a (B*H*W, Cin) @ (Cin, Cout) matmul in
NHWC — a Pallas kernel can accumulate the channel statistics in VMEM as
the matmul epilogue streams tiles out, saving one full HBM read of the
activation per layer.

This script measures, per representative ResNet-50 1x1 shape at batch 128:
  (a) XLA: y = x @ w; s = sum(y); ss = sum(y*y)   (jitted together)
  (b) Pallas: fused kernel emitting y, s, ss in one pass
Timing is the shared scan-amortized discipline in timing_util /
mxnet_tpu.tune.sweep.
Prints one JSON line per shape plus a summary.
"""
from __future__ import annotations

import functools
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as onp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from timing_util import scan_ms  # noqa: E402


def _fused_kernel(x_ref, w_ref, y_ref, s_ref, ss_ref, acc_s, acc_ss):
    mi = pl.program_id(1)
    y = jnp.dot(x_ref[:], w_ref[:],
                preferred_element_type=jnp.float32)

    @pl.when(mi == 0)
    def _init():
        acc_s[:] = jnp.zeros_like(acc_s)
        acc_ss[:] = jnp.zeros_like(acc_ss)

    acc_s[:] += jnp.sum(y, axis=0, keepdims=True)
    acc_ss[:] += jnp.sum(y * y, axis=0, keepdims=True)
    y_ref[:] = y.astype(y_ref.dtype)

    @pl.when(mi == pl.num_programs(1) - 1)
    def _finish():
        s_ref[:] = acc_s[:]
        ss_ref[:] = acc_ss[:]


def _pick_tile(m, target=512):
    tm = min(target, m)
    while m % tm or tm % 8:
        tm -= 8
    return max(tm, 8)


@functools.partial(jax.jit, static_argnames=("tm", "tn"))
def matmul_bn_stats_pallas(x, w, tm=None, tn=256):
    m, k = x.shape
    _, n = w.shape
    tn = min(tn, n)
    tm = tm or _pick_tile(m)
    grid = (n // tn, m // tm)  # m innermost: stats block stays resident
    return pl.pallas_call(
        _fused_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((tm, k), lambda ni, mi: (mi, 0)),
            pl.BlockSpec((k, tn), lambda ni, mi: (0, ni)),
        ],
        out_specs=[
            pl.BlockSpec((tm, tn), lambda ni, mi: (mi, ni)),
            pl.BlockSpec((1, tn), lambda ni, mi: (0, ni)),
            pl.BlockSpec((1, tn), lambda ni, mi: (0, ni)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((m, n), x.dtype),
            jax.ShapeDtypeStruct((1, n), jnp.float32),
            jax.ShapeDtypeStruct((1, n), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((1, tn), jnp.float32),
            pltpu.VMEM((1, tn), jnp.float32),
        ],
    )(x, w)


@jax.jit
def matmul_bn_stats_xla(x, w):
    y = jnp.dot(x, w, preferred_element_type=jnp.float32)
    s = jnp.sum(y, axis=0)
    ss = jnp.sum(y * y, axis=0)
    return y.astype(x.dtype), s, ss


SHAPES = [  # (M=B*H*W, K=Cin, N=Cout) for batch-128 ResNet-50 1x1 convs
    (128 * 56 * 56, 64, 256),
    (128 * 56 * 56, 256, 64),
    (128 * 28 * 28, 256, 512),
    (128 * 28 * 28, 512, 128),
    (128 * 14 * 14, 512, 1024),
    (128 * 14 * 14, 1024, 256),
    (128 * 7 * 7, 1024, 2048),
    (128 * 7 * 7, 2048, 512),
]


def main():
    rs = onp.random.RandomState(0)
    speedups = []
    for m, k, n in SHAPES:
        x = jax.device_put(rs.randn(m, k).astype(onp.float32).astype(
            jnp.bfloat16))
        w = jax.device_put(rs.randn(k, n).astype(onp.float32).astype(
            jnp.bfloat16))
        # correctness first
        y1, s1, ss1 = matmul_bn_stats_xla(x, w)
        y2, s2, ss2 = matmul_bn_stats_pallas(x, w)
        onp.testing.assert_allclose(onp.asarray(s1), onp.asarray(s2)[0],
                                    rtol=2e-2)
        onp.testing.assert_allclose(onp.asarray(y1, onp.float32),
                                    onp.asarray(y2, onp.float32), rtol=5e-2,
                                    atol=1.0)
        ms_xla, _, ok_xla = scan_ms(matmul_bn_stats_xla, (x, w))
        ms_pal, _, ok_pal = scan_ms(matmul_bn_stats_pallas, (x, w))
        speedups.append(ms_xla / ms_pal)
        print(json.dumps({
            "shape": [m, k, n],
            "xla_ms": round(ms_xla, 3),
            "pallas_ms": round(ms_pal, 3),
            "speedup": round(ms_xla / ms_pal, 3),
            "reliable": ok_xla and ok_pal,
        }), flush=True)
    print(json.dumps({"geomean_speedup": round(
        float(onp.exp(onp.mean(onp.log(speedups)))), 3)}))


if __name__ == "__main__":
    main()
