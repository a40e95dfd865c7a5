"""Sweep runner — ONE timing/trimming implementation for every sweep.

The hand-rolled bench loops (``attention_bench --block-sweep``,
``flash_roofline_experiment``) each re-grew their own warmup/median
logic; this module is the single copy they and ``tools/autotune`` now
share.  Two measurement modes:

* ``time`` — real device timing with the ``benchmark/timing_util.py``
  discipline (scan-amortized, drain-subtracted, warmup + trimmed
  median over repeats), optionally one subprocess per candidate like
  bench.py's census rider so a Mosaic crash or VMEM blow-up in one
  candidate cannot take down the sweep.
* ``model`` — deterministic roofline scoring against the census PEAKS
  (``analysis/census.py``): MXU/HBM/VPU terms plus a per-grid-step
  overhead.  This is what CI re-verifies committed winners with — no
  timing noise, same verdict on every machine.
"""
from __future__ import annotations

import json
import math
import os
import subprocess
import sys
import time

import numpy as onp

__all__ = [
    "scan_ms", "window_iters", "measured_step_s", "trimmed_median",
    "DRAIN_S", "time_candidate", "model_candidate", "sweep_kernel",
]


# --------------------------------------------------------------------------
# scan-amortized timing (benchmark/timing_util.py delegates here)
# --------------------------------------------------------------------------
DRAIN_S = 0.1   # allowance for one host readback per window


def scan_ms(impl, args, grad=False, max_seconds=12.0):
    """Per-call device ms of ``impl(*args)`` (or its value+grad when
    ``grad``), via a chained lax.scan.  Returns (ms, scan_len, reliable).

    The first element of ``args`` is the scan carry; the rest close over.
    ``grad=True`` differentiates w.r.t. the carry only; ``grad="all"``
    w.r.t. every positional arg (the attention benches time the full
    dq/dk/dv backward, not just dq).
    """
    import jax
    import jax.numpy as jnp

    c0, rest = args[0], tuple(args[1:])

    if grad:
        argnums = tuple(range(1 + len(rest))) if grad == "all" else (0,)
        gfn = jax.value_and_grad(
            lambda c, *r: impl(c, *r).sum().astype(jnp.float32),
            argnums=argnums)

        def body(c, _):
            val, grads = gfn(c, *rest)
            dep = (val + sum(g.astype(jnp.float32).sum()
                             for g in grads)) * 1e-24
            return c + dep.astype(c.dtype), None
    else:
        def body(c, _):
            out = impl(c, *rest)
            dep = jax.tree_util.tree_reduce(
                lambda a, x: a + x.astype(jnp.float32).sum(),
                out, jnp.float32(0.0)) * 1e-24
            return c + dep.astype(c.dtype), None

    def make(n):
        @jax.jit
        def run(c):
            c, _ = jax.lax.scan(body, c, None, length=n)
            return c
        return run

    def drain(x):
        onp.asarray(jax.tree_util.tree_leaves(x)[0].ravel()[0])

    drain(c0)
    t_sync = min((lambda t0: (drain(c0),
                              time.perf_counter() - t0)[1])(
        time.perf_counter()) for _ in range(3))

    run2 = make(2)
    drain(run2(c0))
    t0 = time.perf_counter()
    drain(run2(c0))
    est = max((time.perf_counter() - t0 - t_sync) / 2, 1e-5)
    n = int(min(max(6.0 * t_sync / est, 8), 4096, max_seconds / est))
    n = max(n, 8)
    for attempt in range(2):
        run_n = make(n)
        drain(run_n(c0))
        best = None
        for _ in range(3):
            t0 = time.perf_counter()
            drain(run_n(c0))
            best = min(best or 1e9, time.perf_counter() - t0)
        work = best - t_sync
        if work >= 2 * t_sync or attempt == 1:
            break
        per = max(work / n, 1e-7)
        n2 = int(min(max(6.0 * t_sync / per, n * 4), 4096,
                     max_seconds / per))
        if n2 == n:
            break
        n = n2
    return max(work / n, 1e-9) * 1e3, n, work >= 2 * t_sync


def window_iters(est_step_s, target_s=3.0, min_iters=10, max_iters=5000):
    """Size a throughput window from a measured per-step time so the
    closing drain stays a small fraction of it (~3% at the 3 s default).
    The iteration cap is a runaway guard only — it must stay far above
    target_s / fastest-real-step (~2 ms)."""
    return int(min(max(target_s / max(est_step_s, 1e-4), min_iters),
                   max_iters))


def measured_step_s(run_step, drain, n=3):
    """Per-step seconds from ``n`` steps + one drain (DRAIN_S subtracted)
    — the probe every bench feeds into :func:`window_iters`."""
    t0 = time.perf_counter()
    for _ in range(n):
        run_step()
    drain()
    return max((time.perf_counter() - t0 - DRAIN_S) / n, 1e-3)


def trimmed_median(samples, trim=0.25):
    """Median of the samples left after dropping ``floor(n*trim)`` from
    each tail — the sweep's one trimming rule (outliers come from GC
    pauses and host hiccups, symmetric trim kills both tails)."""
    xs = sorted(samples)
    k = int(len(xs) * trim)
    xs = xs[k:len(xs) - k] or xs
    mid = len(xs) // 2
    if len(xs) % 2:
        return xs[mid]
    return 0.5 * (xs[mid - 1] + xs[mid])


# --------------------------------------------------------------------------
# candidate measurement
# --------------------------------------------------------------------------
def time_candidate(kernel, signature, params, repeats=3, max_seconds=8.0):
    """Trimmed-median ms for one candidate, in-process.

    Returns ``{"ms", "samples", "scan_len", "reliable"}``."""
    from . import kernels as _kernels
    spec = _kernels.get(kernel)
    impl, args, grad = spec.build(signature, params)
    samples, scan_len, reliable = [], 0, True
    for _ in range(max(repeats, 1)):
        ms, n, ok = scan_ms(impl, args, grad=grad, max_seconds=max_seconds)
        samples.append(ms)
        scan_len = n
        reliable = reliable and ok
    return {"ms": trimmed_median(samples), "samples": samples,
            "scan_len": scan_len, "reliable": reliable}


def time_candidate_isolated(kernel, signature, params, repeats=3,
                            max_seconds=8.0, timeout=600):
    """One candidate in a fresh interpreter (bench.py census-rider
    style): a Mosaic crash, VMEM blow-up or hang in one
    candidate surfaces as that candidate's ``error`` row instead of
    killing the sweep."""
    code = (
        "import json\n"
        "from mxnet_tpu.tune import sweep\n"
        f"r = sweep.time_candidate({kernel!r}, {signature!r}, "
        f"{params!r}, repeats={repeats}, max_seconds={max_seconds})\n"
        "print('AUTOTUNE_JSON ' + json.dumps(r))\n")
    # mxlint: disable=env-read-at-trace-time -- host-side: forwards the parent env (JAX_PLATFORMS, cache path) to the candidate subprocess; never enters traced code
    proc = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=timeout, env=dict(os.environ))
    for line in proc.stdout.splitlines():
        if line.startswith("AUTOTUNE_JSON "):
            return json.loads(line[len("AUTOTUNE_JSON "):])
    tail = (proc.stderr or proc.stdout or "").strip().splitlines()[-3:]
    return {"error": f"exit {proc.returncode}: " + " | ".join(tail)}


def model_candidate(kernel, signature, params, device=None):
    """Deterministic roofline score (modeled seconds) for one candidate."""
    from ..analysis.census import PEAKS
    from . import kernels as _kernels
    spec = _kernels.get(kernel)
    _, _, dev = _kernels.parse_signature(signature)
    device = device or dev
    if device not in PEAKS:
        raise ValueError(f"no roofline peaks for {device!r} "
                         f"(census.PEAKS has {sorted(PEAKS)})")
    return {"modeled_s": spec.model_time(signature, params, PEAKS[device])}


# --------------------------------------------------------------------------
# the sweep
# --------------------------------------------------------------------------
def sweep_kernel(kernel, signature=None, mode="model", isolate=False,
                 repeats=3, log=None):
    """Sweep one kernel's candidate grid for one signature.

    Returns ``{"kernel", "signature", "mode", "default", "winner",
    "speedup_vs_default", "rows"}`` where rows carry every candidate's
    params + score (``ms`` or ``modeled_s``; failed candidates carry
    ``error`` and never win)."""
    from . import kernels as _kernels
    spec = _kernels.get(kernel)
    signature = signature or spec.signatures()[0]
    grid = spec.grid(signature)
    default = spec.default(signature)
    if not any(p == default for p in grid):
        grid = [default] + list(grid)
    rows = []
    for params in grid:
        if log:
            log(f"  {kernel} {signature} {params} ...")
        if mode == "model":
            row = model_candidate(kernel, signature, params)
        elif isolate:
            row = time_candidate_isolated(kernel, signature, params,
                                          repeats=repeats)
        else:
            try:
                row = time_candidate(kernel, signature, params,
                                     repeats=repeats)
            except Exception as e:          # candidate, not sweep, fails
                row = {"error": f"{type(e).__name__}: {e}"}
        row["params"] = dict(params)
        rows.append(row)

    def score(row):
        if "error" in row:
            return math.inf
        return row.get("ms", row.get("modeled_s", math.inf))

    best_row = min(rows, key=score)
    default_row = next(r for r in rows if r["params"] == default)
    speedup = None
    if score(default_row) != math.inf and score(best_row) > 0:
        speedup = round(score(default_row) / score(best_row), 4)
    return {
        "kernel": kernel, "signature": signature, "mode": mode,
        "default": default, "winner": dict(best_row["params"]),
        "speedup_vs_default": speedup, "rows": rows,
    }
