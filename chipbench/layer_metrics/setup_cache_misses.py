"""Programs that set-up compiled because the persistent cache did not hold
them: the program's `xla.compile` spans before the traced window whose
`cache_hit` is false.  0 in a warm run; what a checkout's first run and a run
after another program's are made of (`chipbench/setup_record.py`)."""
from chipbench import setup_record


def read(trace, spans, cell):
    record = setup_record.load(spans)
    return record and record.count_before("xla.compile", cache_hit=False)
