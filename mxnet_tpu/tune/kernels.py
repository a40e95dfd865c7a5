"""Tunable-kernel registry: what the autotuner can sweep and how.

Each :class:`KernelSpec` owns one tuned kernel's contract with the
cache:

* ``signature(...)``  — the stable key half: shape dims bucketed to the
  next power of two (so T=1000 and T=1024 share one entry), a dtype
  tag, and the device kind.  Dims are sorted so kwargs order can never
  fork the key.
* ``grid(signature)`` — the candidate params, already filtered for
  hard feasibility (VMEM ceiling, block <= dim).
* ``default(signature)`` — the documented static fallback dispatch
  uses on any cache miss; always a member of the swept grid.
* ``build(signature, params)`` — (impl, args, grad) for the time-mode
  sweep, exercising the REAL production code path with the candidate
  params forced.
* ``model_time`` — optional deterministic roofline model (seconds) for
  kernels whose committed winner CI re-derives without a device
  (currently flash attention; see the calibration block below).
"""
from __future__ import annotations

import math
import re

__all__ = [
    "KernelSpec", "get", "names", "device_kind", "pow2_bucket",
    "signature", "parse_signature", "dtype_tag",
]

_DTYPE_TAGS = {"bfloat16": "bf16", "float32": "f32", "float16": "f16"}


def pow2_bucket(n):
    """Next power of two >= n — the shape-bucket rule (one cache entry
    serves every shape in the bucket; the kernel re-clamps at trace
    time, see _pick_block)."""
    n = int(n)
    return 1 if n <= 1 else 1 << (n - 1).bit_length()


def dtype_tag(dtype):
    import jax.numpy as jnp
    name = jnp.dtype(dtype).name
    tag = _DTYPE_TAGS.get(name)
    if tag is None:
        raise ValueError(f"no autotune dtype tag for {name!r}")
    return tag


def tag_dtype(tag):
    import jax.numpy as jnp
    for name, t in _DTYPE_TAGS.items():
        if t == tag:
            return jnp.dtype(name)
    raise ValueError(f"unknown dtype tag {tag!r}")


def device_kind():
    """The census PEAKS key of the device on a TPU backend (an unknown
    chip is an error); the census DEFAULT_DEVICE everywhere else (the CPU
    mesh models a v5e pod throughout this repo — hloscan contracts,
    census artifacts — so the committed v5e entries are live on it)."""
    import jax
    from ..analysis.census import DEFAULT_DEVICE, peaks_key
    dev = jax.devices()[0]
    if dev.platform == "tpu":
        return peaks_key(dev.device_kind)
    return DEFAULT_DEVICE


def signature(dtype, device=None, **dims):
    """``<dim-buckets>|<dtype>|<device>`` — e.g.
    ``b8.d64.h8.t4096|bf16|tpu-v5e``."""
    bucket = ".".join(f"{k}{pow2_bucket(v)}" for k, v in sorted(dims.items()))
    return f"{bucket}|{dtype_tag(dtype)}|{device or device_kind()}"


def parse_signature(sig):
    """-> (dims dict, dtype tag, device kind)."""
    bucket, dtype, device = sig.split("|")
    dims = {}
    for tok in bucket.split("."):
        m = re.fullmatch(r"([a-z]+)(\d+)", tok)
        if not m:
            raise ValueError(f"bad shape-bucket token {tok!r} in {sig!r}")
        dims[m.group(1)] = int(m.group(2))
    return dims, dtype, device


class KernelSpec:
    def __init__(self, name, signatures, grid, default, build,
                 model_time=None):
        self.name = name
        self.signatures = signatures
        self.grid = grid
        self.default = default
        self.build = build
        self._model_time = model_time

    def model_time(self, sig, params, peaks):
        if self._model_time is None:
            raise ValueError(
                f"kernel {self.name!r} has no deterministic model — sweep "
                f"it in time mode (tools/autotune --mode time)")
        return self._model_time(sig, params, peaks)


# ===========================================================================
# flash attention (ops/pallas_kernels.py)
# ===========================================================================
# Roofline model, calibrated against the committed block-sweep ablation
# (benchmark/results/flash_roofline_tpu_v5e.json):
#   * the per-block VPU softmax chain was measured at ~half of kernel
#     time and is the term wider K blocks amortize (fewer m/l merge +
#     acc-rescale rounds): chain = K_CHAIN * b*h*t^2 / bk;
#   * K blocks of 1024 beat 512 by 1.68x fwd — fixed by K_CHAIN and the
#     per-grid-step bubble (peaks launch_s) given the MXU/HBM terms;
#   * bk=2048 ties 1024: its f32 score block pushes the working set
#     over the ~4 MiB soft budget, costing the revolving-buffer overlap
#     (chain + step terms x2) — exactly cancelling the halved rounds.
#     A vmem-proportional epsilon then prefers the smaller footprint;
#   * wider q blocks do nothing (1024x512 ~= 512x512): only the K/V
#     reread term t/bq moves, a few % of total.
_F_ELEM_S = 1.8627e-13      # s per score element (vectorized exp/mul chain)
_F_CHAIN_S = 8.196e-10      # s per (row x k-round): serialized m/l merge
_F_VMEM_SOFT = 4 * 2**20    # above: revolving-buffer overlap lost (x2)
_F_VMEM_HARD = 8 * 2**20    # above: does not fit alongside semaphores/bwd
_F_VMEM_EPS = 1e-16         # s/byte tie-break toward the smaller footprint


def _flash_vmem(bq, bk, d, ebytes):
    """Fwd working-set estimate: double-buffered q/k/v streams, the f32
    score block, the f32 output accumulator, m/l columns."""
    return (2 * ebytes * (bq * d + 2 * bk * d)   # q + k,v streams, 2-deep
            + 4 * bq * bk                        # f32 scores/probs
            + 4 * bq * d                         # f32 acc
            + 8 * bq)                            # m, l


def _flash_sigs():
    return [signature("bfloat16", b=8, h=8, t=4096, d=64),
            signature("bfloat16", b=8, h=8, t=8192, d=64)]


def _flash_grid(sig):
    dims, dtype, _ = parse_signature(sig)
    t, d = dims["t"], dims["d"]
    ebytes = tag_dtype(dtype).itemsize
    out = []
    for bq in (256, 512, 1024, 2048):
        for bk in (256, 512, 1024, 2048):
            if bq > t or bk > t:
                continue
            if _flash_vmem(bq, bk, d, ebytes) > _F_VMEM_HARD:
                continue
            out.append({"block_q": bq, "block_k": bk})
    return out


def _flash_default(sig):
    # pallas_kernels._BLOCK_TARGET_Q/_K — the documented static fallback
    return {"block_q": 512, "block_k": 1024}


def _flash_model(sig, params, peaks):
    dims, dtype, _ = parse_signature(sig)
    b, h, t, d = dims["b"], dims["h"], dims["t"], dims["d"]
    ebytes = tag_dtype(dtype).itemsize
    bq = min(params["block_q"], t)
    bk = min(params["block_k"], t)
    t_mxu = 4.0 * b * h * t * t * d / peaks["flops"]        # QK^T + PV
    io = b * h * t * d * ebytes
    t_hbm = (2 * io + 2 * io * (t / bq)) / peaks["bw"]      # q+o; k,v reread
    rows = b * h * t * t
    t_elem = _F_ELEM_S * rows
    t_chain = _F_CHAIN_S * rows / bk
    n_steps = b * h * (t / bq) * (t / bk)
    t_step = peaks["launch_s"] * n_steps
    vmem = _flash_vmem(bq, bk, d, ebytes)
    pen = 2.0 if vmem > _F_VMEM_SOFT else 1.0
    return t_mxu + t_hbm + t_elem + pen * (t_chain + t_step) \
        + _F_VMEM_EPS * vmem


def _flash_build(sig, params):
    import jax
    from ..ops.pallas_kernels import flash_attention
    dims, dtype, _ = parse_signature(sig)
    dt = tag_dtype(dtype)
    b, h, t, d = dims["b"], dims["h"], dims["t"], dims["d"]
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q, k, v = (jax.random.normal(kk, (b, h, t, d), dtype=dt) for kk in ks)
    bq, bk = params["block_q"], params["block_k"]

    def impl(q, k, v):
        return flash_attention(q, k, v, causal=True, block_q=bq, block_k=bk)
    return impl, (q, k, v), False


# ===========================================================================
# scan-LSTM cell (gluon/rnn/rnn_layer.py)
# ===========================================================================
def _lstm_sigs():
    # the rnn_lm bench shape: b=32, bptt=35, hidden=650
    return [signature("bfloat16", b=32, t=35, h=650)]


def _lstm_grid(sig):
    return [{"unroll": u, "gate_layout": gl}
            for u in (1, 2, 4, 8) for gl in ("fused", "split")]


def _lstm_default(sig):
    # pre-tune production behavior: plain scan, fused 4H gate matmul
    return {"unroll": 1, "gate_layout": "fused"}


def _lstm_build(sig, params):
    import jax
    from ..gluon.rnn.rnn_layer import _run_single_direction
    dims, dtype, _ = parse_signature(sig)
    dt = tag_dtype(dtype)
    b, t, h = dims["b"], dims["t"], dims["h"]
    ks = jax.random.split(jax.random.PRNGKey(1), 6)
    x = jax.random.normal(ks[0], (t, b, h), dtype=dt)
    i2h_w = jax.random.normal(ks[1], (4 * h, h), dtype=dt) * 0.05
    h2h_w = jax.random.normal(ks[2], (4 * h, h), dtype=dt) * 0.05
    i2h_b = jax.random.normal(ks[3], (4 * h,), dtype=dt) * 0.05
    h2h_b = jax.random.normal(ks[4], (4 * h,), dtype=dt) * 0.05
    h0 = jax.numpy.zeros((b, h), dtype=dt)
    c0 = jax.numpy.zeros((b, h), dtype=dt)
    u, gl = params["unroll"], params["gate_layout"]

    def impl(x):
        out, _, _ = _run_single_direction(
            "lstm", x, h0, c0, i2h_w, i2h_b, h2h_w, h2h_b,
            unroll=u, gate_layout=gl)
        return out
    return impl, (x,), False


# ===========================================================================
# space-to-depth ResNet stem (ops/stem.py)
# ===========================================================================
def _stem_sigs():
    # the census resnet_profile stem shape: (8, 3, 64, 64) -> C=64
    return [signature("bfloat16", b=8, c=64, h=64, w=64)]


def _stem_dims(sig):
    dims, dtype, _ = parse_signature(sig)
    m = dims["b"] * (dims["h"] // 2) * (dims["w"] // 2)
    return m, dims["c"], 192, tag_dtype(dtype).itemsize   # K = 4*3*16


def _stem_grid(sig):
    m, n, _, _ = _stem_dims(sig)
    return [{"tm": tm, "tn": tn}
            for tm in (128, 256, 512, 1024) if tm <= m
            for tn in (64, 128, 256) if tn <= n]


def _stem_default(sig):
    # ops/stem.py STEM_TILE_DEFAULT — shape-agnostic targets the kernel
    # re-fits with _fit_tile (keep in sync)
    return {"tm": 512, "tn": 128}


def _stem_model(sig, params, peaks):
    """Roofline for the (M, 192) @ (192, C) stem matmul: the K=192
    contraction is never split, so a candidate only moves the reread
    and per-grid-step terms — patches stream once per N-block, the
    weight panel once per M-block, plus the dispatch floor per step.
    Wider tiles win until VMEM pressure (eps tie-break) argues back."""
    m, n, k, e = _stem_dims(sig)
    tm = min(params["tm"], m)
    tn = min(params["tn"], n)
    steps = (m / tm) * (n / tn)
    t_mxu = 2.0 * m * n * k / peaks["flops"]
    t_hbm = (m * k * e * (n / tn)        # patch tiles, reread per N-block
             + k * n * e * (m / tm)      # weight panel, reread per M-block
             + m * n * e) / peaks["bw"]  # output, written once
    t_step = peaks["launch_s"] * steps
    vmem = e * (tm * k + k * tn) + 4 * tm * tn   # tiles + f32 acc
    return t_mxu + t_hbm + t_step + 1e-16 * vmem


def _stem_build(sig, params):
    import jax
    from ..ops import stem as _stem
    dims, dtype, _ = parse_signature(sig)
    dt = tag_dtype(dtype)
    b, c, h, w = dims["b"], dims["c"], dims["h"], dims["w"]
    ks = jax.random.split(jax.random.PRNGKey(2), 2)
    x = jax.random.normal(ks[0], (b, 3, h, w), dtype=dt)
    w7 = jax.random.normal(ks[1], (c, 3, 7, 7), dtype=dt) * 0.05
    xs = _stem.space_to_depth2(x)
    wf = _stem.fold_stem_kernel(w7)
    tm, tn = params["tm"], params["tn"]

    def impl(xs):
        return _stem.stem_conv_pallas(xs, wf, tm=tm, tn=tn)
    return impl, (xs,), False


# ===========================================================================
_REGISTRY = {
    "flash_attention": KernelSpec(
        "flash_attention", _flash_sigs, _flash_grid, _flash_default,
        _flash_build, model_time=_flash_model),
    "lstm_cell": KernelSpec(
        "lstm_cell", _lstm_sigs, _lstm_grid, _lstm_default, _lstm_build),
    "stem_s2d": KernelSpec(
        "stem_s2d", _stem_sigs, _stem_grid, _stem_default, _stem_build,
        model_time=_stem_model),
}


def get(kernel):
    spec = _REGISTRY.get(kernel)
    if spec is None:
        raise KeyError(
            f"unknown tunable kernel {kernel!r} (have: {sorted(_REGISTRY)})")
    return spec


def names():
    return sorted(_REGISTRY)
