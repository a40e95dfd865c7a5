"""Median host time of the jitted call inside `FusedTrainStep.step` over the
traced window's steps (argument marshalling and the enqueue), from the program's
`fused_step.launch` spans (`chipbench/program_record.py`)."""
from chipbench import program_record


def read(trace, spans, cell):
    record = program_record.load(spans)
    return record and record.median_ms("fused_step.launch")
