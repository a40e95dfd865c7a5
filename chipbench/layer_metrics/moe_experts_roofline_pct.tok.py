"""Share of their roofline that the experts' grouped matmuls reach: the
larger of FLOP time (6 * 3*U*F for every routed row on a held expert, rows
from the program's `expert_load` counters of the last step) and HBM time of
their bytes (`expert_bytes` in the configuration's module), over the device
time a step spends in the `ragged-dot` Mosaic calls that `lax.ragged_dot`
lowers to.  None where the window holds none, or the program has no
counter."""
from chipbench import layer_work
from chipbench.trace import is_custom_call


def is_grouped_matmul(label):
    return is_custom_call(label) and "ragged" in label


def read(trace, spans, cell):
    seconds = layer_work.per_step_s(trace, is_grouped_matmul)
    rows = layer_work.routed_rows()
    if seconds is None or not rows:
        return None
    cfg, mod = layer_work.config_of(cell)
    peak = layer_work.peaks()
    least = max(mod.expert_flops(cfg, rows) / peak["bf16_flops_per_s"],
                mod.expert_bytes(cfg, rows) / peak["hbm_bytes_per_s"])
    return 100.0 * least / seconds
