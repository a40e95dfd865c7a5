"""Seconds of set-up covered by the program's `xla.lower` spans: jaxpr to
StableHLO, which for a Pallas call includes lowering its kernel to Mosaic
(`chipbench/setup_record.py`)."""
from chipbench import setup_record


def read(trace, spans, cell):
    record = setup_record.load(spans)
    return record and record.covered_before_s(("xla.lower",))
