"""Share of device 0's busy time spent in Mosaic (Pallas) custom calls; None
where the step holds none."""
from chipbench.trace import is_custom_call


def read(trace, spans, cell):
    if not any(is_custom_call(name) for name, _s, _e in trace.ops[0]):
        return None
    return trace.busy_share_pct(is_custom_call, 0)
