"""Scan-amortized device timing (shared helper).

A dispatch loop adds a host round trip to every call, which buries a
sub-millisecond kernel.  The discipline used across benchmark/: chain N
calls inside one `lax.scan`, feeding a 1e-24-scaled summary of each
output back into the carry so nothing is hoisted or dead-coded, measure
the closing drain separately and subtract, and require scan work >= 2x
drain for a `reliable` row.

The implementation now lives in ``mxnet_tpu.tune.sweep`` — the
autotuner's sweep runner — so the benches and ``tools/autotune`` share
ONE timing/trimming discipline.  This module is the benches' import
shim (benchmark/ is not a package).
"""
from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from mxnet_tpu.tune.sweep import (  # noqa: E402,F401
    DRAIN_S,
    measured_step_s,
    scan_ms,
    trimmed_median,
    window_iters,
)
