"""Seconds of set-up covered by the program's `xla.compile` spans: the
backend's part of a program, which is a load from the persistent cache or a
compile (`setup_cache_misses` counts the compiles; `chipbench/setup_record.py`)."""
from chipbench import setup_record


def read(trace, spans, cell):
    record = setup_record.load(spans)
    return record and record.covered_before_s(("xla.compile",))
