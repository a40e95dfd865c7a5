"""Share of their roofline that the flash-attention kernels reach: the
larger of FLOP time and HBM time of the step's attention over the visible
band (`attention_flops`, `attention_bytes` in the configuration's module; a
masked diagonal block counts only its visible part, work recomputed in the
backward pass counts once), over the device time a step spends in the flash
Mosaic calls.  They are found by the `name=` of their `pallas_call`
(`flash_fwd`, `flash_bwd_dq`, `flash_bwd_dkv`), else by the result shapes
the cell file lists.  None where the window holds none."""
from chipbench import layer_work
from chipbench.trace import is_custom_call


def is_flash(label, cell):
    return is_custom_call(label) and "ragged" not in label and (
        "flash" in label
        or any(label.endswith(shape) for shape in cell.get("flash_result_shapes", [])))


def read(trace, spans, cell):
    seconds = layer_work.per_step_s(trace, lambda label: is_flash(label, cell))
    if seconds is None:
        return None
    cfg, mod = layer_work.config_of(cell)
    peak = layer_work.peaks()
    least = max(mod.attention_flops(cfg, cell, 1) / peak["bf16_flops_per_s"],
                mod.attention_bytes(cfg, cell, 1) / peak["hbm_bytes_per_s"])
    return 100.0 * least / seconds
