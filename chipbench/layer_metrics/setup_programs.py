"""Programs compiled, or loaded from the persistent cache, before the traced
window: the program's `xla.compile` spans that ended before it
(`chipbench/program_record.py`)."""
from chipbench import program_record


def read(trace, spans, cell):
    record = program_record.load(spans)
    return record and record.count_before("xla.compile")
