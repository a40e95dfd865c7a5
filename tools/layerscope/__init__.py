"""layerscope: per-layer speed-of-light census with roofline attribution.

hloscan (PR 7) gates structural claims in the compiled artifact; this
tool gates the *performance shape*: where each compiled step spends its
FLOPs and bytes, layer by layer, against the chip roofline.  The heavy
lifting — name-scope bucketing, the per-instruction cost model, bound
classification, MFU-floor contracts — lives in
``mxnet_tpu/analysis/census.py``; this package is the driver: entry
capture, the text table, the JSON artifact
(``benchmark/results/layer_census_<entry>.json``), the telemetry
gauges, and the baseline gate CI runs (``tools/layerscope_baseline.json``,
checked in EMPTY — all known offenders are waived on the contract with
reasons, same policy as hloscan).

The census is cost-model-only (bound classes and speed-of-light MFU
from modeled FLOPs/bytes against the target chip's peaks); times on the
chip come from the benchmark (``chipbench/``, ``PERF.md``).

Usage::

    python -m tools.layerscope                          # all entries
    python -m tools.layerscope --entry fused_train_step_dp
    python -m tools.layerscope --entry resnet_profile --verdicts
"""
from .driver import main, render_table, run, top_sag, verdict_lines  # noqa: F401
