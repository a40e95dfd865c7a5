"""Seconds of set-up covered by the program's `xla.trace` spans: Python
tracing of jitted functions, the outermost trace of each and only those of
10 ms or more (`chipbench/setup_record.py`).  With `setup_lower_s` and
`setup_backend_s` it splits `setup_compile_s`, which reads the three's union."""
from chipbench import setup_record


def read(trace, spans, cell):
    record = setup_record.load(spans)
    return record and record.covered_before_s(("xla.trace",))
