"""Seconds of set-up covered by the program's `block.settle_shapes` spans:
the eager forward with which a block whose parameters wait for their shapes
infers them, a small program a shape (`chipbench/setup_record.py`).  Their
compiles are inside it, so it overlaps `setup_compile_s`."""
from chipbench import setup_record


def read(trace, spans, cell):
    record = setup_record.load(spans)
    return record and record.covered_before_s(("block.settle_shapes",))
