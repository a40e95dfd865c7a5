"""Median host time of `FusedTrainStep._prepare` over the traced window's steps
(per-step scalars, treedef, mesh placement, two host-to-device puts), from the
program's `fused_step.prepare` spans (`chipbench/program_record.py`)."""
from chipbench import program_record


def read(trace, spans, cell):
    record = program_record.load(spans)
    return record and record.median_ms("fused_step.prepare")
