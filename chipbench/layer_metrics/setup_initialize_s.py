"""Seconds of set-up covered by the program's `block.initialize` spans:
`Block.initialize` giving every parameter of known shape its first value on the
device, with the small programs that takes (`chipbench/program_record.py`).  Their
compiles are inside it, so it overlaps `setup_compile_s`.  A parameter whose
shape waits for the first forward is made there, outside these spans."""
from chipbench import program_record


def read(trace, spans, cell):
    record = program_record.load(spans)
    return record and record.covered_before_s(("block.initialize",))
