"""Seconds of set-up (everything before the traced window: build, shape
settling, warm-up) covered by the union of the program's `xla.trace`, `xla.lower`
and `xla.compile` spans, which jax reports per function and the program records
(`chipbench/program_record.py`).  A load from the persistent cache is an
`xla.compile` span too; a trace shorter than 10 ms, as an eager op's is, is not
on the record."""
from chipbench import program_record


def read(trace, spans, cell):
    record = program_record.load(spans)
    return record and record.covered_before_s(program_record.XLA_STAGES)
