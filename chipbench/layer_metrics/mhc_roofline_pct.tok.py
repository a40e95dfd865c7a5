"""Share of its roofline that mHC's residual path reaches: the larger of
FLOP time and HBM time of the step's mixes (`mhc_flops`, `mhc_bytes` in the
configuration's module: each stream tensor read or written once per pass that
must touch it, work recomputed in the backward pass once), over the device
time a step spends in them.  Its rows are the Mosaic calls with `mhc` in
their name, else the rows whose label holds one of the shapes the cell file
lists under `mhc_result_shapes` (the streams and one stream's slice, the
mixes' logits and their Sinkhorn matrices); the time is the UNION of those
rows' intervals.  None
where the window holds none, or the configuration's module counts no such
work."""
from chipbench import layer_work
from chipbench.trace import is_custom_call, total


def is_mhc(label, cell):
    if is_custom_call(label):
        return "mhc" in label
    return any(shape in label for shape in cell.get("mhc_result_shapes", []))


def seconds_per_step(trace, cell):
    """Device 0's seconds per step in mHC's rows; None where there are none."""
    steps = sum(name == "chipbench.dispatch" for name, _s, _e in trace.spans)
    busy = total(trace.busy(0, lambda label: is_mhc(label, cell)))
    return busy / steps if steps and busy else None


def read(trace, spans, cell):
    seconds = seconds_per_step(trace, cell)
    cfg, mod = layer_work.config_of(cell)
    if seconds is None or not hasattr(mod, "mhc_bytes"):
        return None
    peak = layer_work.peaks()
    least = max(mod.mhc_flops(cfg, cell, 1) / peak["bf16_flops_per_s"],
                mod.mhc_bytes(cfg, cell, 1) / peak["hbm_bytes_per_s"])
    return 100.0 * least / seconds
