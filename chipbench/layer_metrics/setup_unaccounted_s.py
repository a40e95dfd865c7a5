"""Seconds from the process's start to the END of the program's first
`fused_step.step` span that no span on record covers: what nothing explains
before the first step is dispatched.  The warm-up after it runs at the
device's pace and is the benchmark's (`chipbench/setup_record.py`)."""
from chipbench import setup_record


def read(trace, spans, cell):
    record = setup_record.load(spans)
    return record and setup_record.unaccounted_s(record.before)
