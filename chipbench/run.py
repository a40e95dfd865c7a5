"""One run of one benchmark cell on the attached TPU.

    python3 -m chipbench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Finds the cell's files by the names in BENCHMARK.json (README.md), builds the
step from the seed, warms up the cell's own shapes, measures one window and
prints one JSON object as its last line.  A run that finds no TPU, or fewer
chips than the cell asks for, exits non-zero and prints no result.
"""
from __future__ import annotations

import time

_T0 = time.perf_counter()   # process start, as near as this module can see it

import argparse
import collections
import contextlib
import glob
import importlib.util
import json
import math
import os
import sys
import tempfile

import numpy

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
PLATFORM = "tpu"            # the only platform measured; tests patch it to rehearse
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
WARMUP_STEPS = 8            # on the ring's first batch; their losses are checked
LAG = 2                     # steps kept in flight while the window is timed


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_py(path):
    """A module from a file whose name may hold dots (`dispatch_ms.img.py`)."""
    name = "chipbench_" + os.path.basename(path)[:-3].replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def metrics_of(manifest, section, cell_name):
    """The manifest's metrics of one section that this cell reports."""
    return [m for m in manifest[section]
            if cell_name in m.get("workloads", [cell_name])]


def load_cell(name):
    """(manifest, chips, cell, cfg, config module) of the cell `name`."""
    manifest = load_json(ROOT, "BENCHMARK.json")
    entry = next((w for w in manifest["workloads"] if w["name"] == name), None)
    if entry is None:
        raise SystemExit(f"BENCHMARK.json has no workload {name!r}")
    cfg_entry = next(c for c in manifest["configs"] if c["name"] == entry["config"])
    cfg_file = os.path.join(ROOT, cfg_entry["file"])
    return (manifest, entry["chips"], load_json(HERE, "workloads", name + ".json"),
            load_json(cfg_file), load_py(cfg_file[:-len("json")] + "py"))


def run_window(dispatch, wait, seconds, clock=time.perf_counter, steps=None):
    """Dispatch steps for `seconds` (or exactly `steps` of them) with LAG in
    flight: after dispatching step k the host blocks on step k-LAG and stamps
    its completion, so the device sets the pace and the queue never drains
    before the end.  Returns (begin, completion stamps of every step)."""
    pending, stamps = collections.deque(), []
    begin, k = clock(), 0
    while True:
        pending.append(dispatch(k))
        k += 1
        if len(pending) > LAG:
            wait(pending.popleft())
            stamps.append(clock())
        done = k >= steps if steps else clock() - begin >= seconds
        if done:
            break
    while pending:
        wait(pending.popleft())
        stamps.append(clock())
    return begin, stamps


def interval_percentile_ms(stamps, q):
    """The q-th percentile (interpolated between ranks) of the intervals
    between consecutive completion stamps, in milliseconds."""
    return float(numpy.percentile(numpy.diff(stamps), q)) * 1e3


def is_correct(compiles_in_window, failed, warmup_losses, band):
    """Nothing compiled inside the window, every loss read in it was finite,
    and the warm-up losses start inside the cell's band and fall."""
    lo, hi = band["first"]
    return (compiles_in_window == 0 and failed == 0
            and all(math.isfinite(v) for v in warmup_losses)
            and lo <= warmup_losses[0] <= hi and warmup_losses[-1] < warmup_losses[0])


def compared(compiles_in_window, failed, warmup_losses, band):
    """Each number that `is_correct` compares, beside its limit, in that order."""
    lo, hi = band["first"]
    return {"compiles_in_window": {"value": compiles_in_window, "limit": "== 0"},
            "failed_steps": {"value": failed, "limit": "== 0"},
            "nonfinite_warmup_losses": {"value": sum(not math.isfinite(v) for v in warmup_losses),
                                        "limit": "== 0"},
            "first_warmup_loss": {"value": warmup_losses[0], "limit": [lo, hi]},
            "warmup_loss_change": {"value": warmup_losses[-1] - warmup_losses[0],
                                   "limit": "< 0"}}


def program_facts(compiled):
    """What the timed step's program holds, as `chip_smoke.compiled_step` reads it."""
    text, mem = compiled.as_text(), compiled.memory_analysis()
    return {"tpu_custom_calls": text.count("tpu_custom_call"),
            "all_reduces": text.count(" all-reduce(") + text.count(" all-reduce-start("),
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes}


def peaks_of(kind):
    """The published peaks of a device kind; one the table lacks is an error."""
    peaks = load_json(HERE, "peaks.json")["devices"].get(kind)
    if peaks is None:
        raise SystemExit(f"chipbench/peaks.json has no device kind {kind!r}")
    return peaks


def fuse(mod, trainer, recipe, chips):
    """The cell's `FusedTrainStep`, one program per step, and the sharding of
    its inputs: None on one chip, batch over the recipe's data axis on four."""
    import jax
    import mxnet_tpu as mx
    if not recipe:
        return mx.gluon.FusedTrainStep(mod, trainer), None
    from jax.sharding import NamedSharding
    from mxnet_tpu.parallel.recipe import ShardingRecipe
    recipe = ShardingRecipe(recipe)
    mesh = recipe.build_mesh(jax.devices()[:chips])
    return (mx.gluon.FusedTrainStep(mod, trainer, mesh=mesh, recipe=recipe),
            NamedSharding(mesh, recipe.data_spec()))


def measure(name, manifest, chips, cell, cfg, cfgmod, seed, seconds, traced):
    import jax
    import jax.numpy as jnp

    devices = jax.devices()
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices)}
    if device["platform"] != PLATFORM or len(devices) < chips:
        raise SystemExit(f"cell {name} needs {chips} {PLATFORM} device(s); jax found {device}")
    peak_flops = peaks_of(device["kind"])["bf16_flops_per_s"] * chips

    import mxnet_tpu as mx   # sets the compile-cache directory, here and nowhere else
    marks = {}   # set-up so far, cumulative seconds from process start

    def mark(what):
        marks[what] = time.perf_counter() - _T0
    mark("import")

    # jax leaves out programs that compile in under a second; the ~170 small
    # ones that settle a zoo model's shapes are most of a warm set-up
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, secs, **_: compiles.append(event) if event == COMPILE_EVENT else None)

    mx.random.seed(seed)
    mod, trainer = cfgmod.build(cfg)
    fused, sharding = fuse(mod, trainer, cell.get("recipe"), chips)
    params = list(mod.collect_params().values())
    mark("build")
    ring = cfgmod.make_ring(cfg, cell, chips, seed, sharding)   # [(args, work)], on the device
    mark("ring")
    batch = cell["batch"] * chips

    def step(k):
        args, _work = ring[k % len(ring)]
        return fused(*args, batch_size=batch)._data

    def mean(loss):
        return float(jnp.mean(loss.astype(jnp.float32)))

    warm = [mean(step(0))]
    mark("first_step")
    warm += [mean(step(0)) for _ in range(WARMUP_STEPS - 1)]
    jax.block_until_ready([step(k) for k in range(len(ring))])
    n_compiles = len(compiles)

    # the window: spans cost nothing when no trace is being taken
    span = jax.profiler.TraceAnnotation if traced else (lambda _name: contextlib.nullcontext())
    losses = []

    def dispatch(k):
        with span("chipbench.dispatch"):
            losses.append(step(k))
        return losses[-1]

    def wait(loss):
        with span("chipbench.wait"):
            loss.block_until_ready()

    with tempfile.TemporaryDirectory() as tmp:
        if traced:
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0     # the benchmark's own spans are enough
            jax.profiler.start_trace(tmp, profiler_options=options)
        begin, stamps = run_window(dispatch, wait, seconds,
                                   steps=cell.get("trace_steps", 20) if traced else None)
        jax.block_until_ready([p.data()._data for p in params])  # all the last step wrote
        end = time.perf_counter()
        in_window = len(compiles) - n_compiles
        if traced:
            jax.profiler.stop_trace()
            from chipbench import trace as tracemod
            tr = tracemod.load(glob.glob(os.path.join(
                tmp, "plugins", "profile", "*", "*.xplane.pb"))[0], chips)

    values = jax.device_get([jnp.mean(v.astype(jnp.float32)) for v in losses])
    failed = sum(not math.isfinite(float(v)) for v in values)
    work = sum(ring[k % len(ring)][1] for k in range(len(losses)))
    facts = program_facts(fused.lower(*ring[0][0], batch_size=batch).compile())
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices[:chips])
    # this backend's peak counts live buffers only (PERF.md, PR 21)
    device["memory_peak_bytes"] = peak + facts["temp_bytes"]
    flops = cfgmod.flops_per_step(cfg, cell, chips, mod) * len(losses) / (end - begin)
    print(json.dumps({
        "cell": name, "seed": seed, "steps": len(losses), "window_s": end - begin,
        "setup_marks_s": marks, "step_ms_median": interval_percentile_ms(stamps, 50),
        "warmup_losses": warm, "compiles_in_window": in_window, "program": facts,
        "peak_bytes_in_use": peak, "model_tflops_per_s": flops / 1e12,
        "mfu_bf16": flops / peak_flops,
        "flop_convention": cfgmod.FLOP_CONVENTION}), flush=True)

    result = {"correct": is_correct(in_window, failed, warm, cell["loss_band"]),
              "attempted": len(losses), "failed": failed, "metrics": {}, "device": device}
    if traced:
        for m in metrics_of(manifest, "per_layer", name):
            reader = load_py(os.path.join(HERE, "layer_metrics", m["name"] + ".py"))
            value = reader.read(tr, tr.spans, cell)
            if value is not None:
                result["metrics"][m["name"]] = {"value": value, "unit": m["unit"]}
        device["busy_s"], device["window_s"] = tr.busy_s, tr.window_s
        result["breakdown"] = tr.breakdown()
    else:
        measured = {cfg["throughput_metric"]: work / (end - begin),
                    "step_ms_p95": interval_percentile_ms(stamps, 95),
                    "setup_s": begin - _T0}
        for m in metrics_of(manifest, "end_to_end", name):
            result["metrics"][m["name"]] = {"value": measured[m["name"]], "unit": m["unit"]}
    result["compared"] = compared(in_window, failed, warm, cell["loss_band"])   # the last key
    return result


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    loaded = load_cell(args.workload)
    seconds = args.seconds if args.seconds is not None else loaded[0]["run_seconds"]
    result = measure(args.workload, *loaded, args.seed, seconds, bool(args.trace))
    print(json.dumps(result), flush=True)
    for what, c in result["compared"].items():   # the last lines of standard error
        print(f"compared {what} {c['value']!r} limit {c['limit']}", file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
