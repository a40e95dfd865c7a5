"""Seconds of tracing in the costliest Gluon class: the program's
`block.trace` spans (one block's `forward` on tracers, 10 ms or more), each
less what its `block.trace` children cover, summed by the block's class; the
largest sum (`chipbench/setup_record.py`).  None where no block took 10 ms."""
from chipbench import setup_record


def read(trace, spans, cell):
    record = setup_record.load(spans)
    by_class = record and setup_record.trace_self_s(record.before)
    return max(by_class.values()) if by_class else None
