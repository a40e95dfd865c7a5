"""Share of the device's busy time that a step spends in mHC's residual
path: the union of the intervals of its rows (found as `mhc_roofline_pct.tok`
finds them) over the union of all rows' intervals, device 0.  None where the
window holds no such row."""
import os

from chipbench import run
from chipbench.trace import total

_mhc = run.load_py(os.path.join(run.HERE, "layer_metrics", "mhc_roofline_pct.tok.py"))


def read(trace, spans, cell):
    mixes = total(trace.busy(0, lambda label: _mhc.is_mhc(label, cell)))
    return 100.0 * mixes / total(trace.busy(0)) if mixes else None
