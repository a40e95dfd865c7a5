"""Share of the device's busy time that a step spends in Kimi Delta
Attention's core: the union of the intervals of its rows (found as
`kda_roofline_pct.tok` finds them) over the union of all rows' intervals,
device 0.  None where the window holds no such row."""
import os

from chipbench import run
from chipbench.trace import total

_core = run.load_py(os.path.join(run.HERE, "layer_metrics", "kda_roofline_pct.tok.py"))


def read(trace, spans, cell):
    core = total(trace.busy(0, lambda label: _core.is_kda(label, cell)))
    return 100.0 * core / total(trace.busy(0)) if core else None
