"""The whole step's share of the chip's bf16 peak: the configuration's
`flops_per_step` (model FLOPs, nothing recomputed counted; routed rows from
the program's counter) over the median interval between the completions of
consecutive steps in the traced window (the ends of the benchmark's
`chipbench.wait` spans) and `peaks.json`'s FLOP/s.  None where the window is
too short to have a median."""
import statistics

from chipbench import layer_work


def read(trace, spans, cell):
    ends = sorted(e for name, _s, e in trace.spans if name == "chipbench.wait")
    if len(ends) < 3:
        return None
    cfg, mod = layer_work.config_of(cell)
    step_s = statistics.median(b - a for a, b in zip(ends, ends[1:]))
    chips = len(trace.ops)
    flops = mod.flops_per_step(cfg, cell, chips, None)
    return 100.0 * flops / step_s / (layer_work.peaks()["bf16_flops_per_s"] * chips)
