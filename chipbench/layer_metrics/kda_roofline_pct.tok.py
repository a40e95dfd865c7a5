"""Share of its roofline that Kimi Delta Attention's core reaches: the larger
of FLOP time and HBM time of the step's KDA layers in their chunked form
(`kda_flops`, `kda_bytes` in the configuration's module; work recomputed in
the backward pass counts once), over the device time a step spends in the
core.  Its rows are the Mosaic calls with `kda` in their name, else the rows
whose label holds one of the shapes the cell file lists under
`kda_result_shapes` (the chunk-major tensors of `ops/linear_attention.py`,
and the `while` loops that carry its state).  The time is the UNION of those
rows' intervals: a `while` row spans the rows inside it, and they are not
both counted.  None where the window holds none, or the configuration's
module counts no such work."""
from chipbench import layer_work
from chipbench.trace import is_custom_call, total


def is_kda(label, cell):
    if is_custom_call(label):
        return "kda" in label
    return any(shape in label for shape in cell.get("kda_result_shapes", []))


def core_seconds_per_step(trace, cell):
    """Device 0's seconds per step inside KDA's core; None where there are none."""
    steps = sum(name == "chipbench.dispatch" for name, _s, _e in trace.spans)
    busy = total(trace.busy(0, lambda label: is_kda(label, cell)))
    return busy / steps if steps and busy else None


def read(trace, spans, cell):
    seconds = core_seconds_per_step(trace, cell)
    cfg, mod = layer_work.config_of(cell)
    if seconds is None or not hasattr(mod, "kda_flops"):
        return None
    peak = layer_work.peaks()
    least = max(mod.kda_flops(cfg, cell, 1) / peak["bf16_flops_per_s"],
                mod.kda_bytes(cfg, cell, 1) / peak["hbm_bytes_per_s"])
    return 100.0 * least / seconds
