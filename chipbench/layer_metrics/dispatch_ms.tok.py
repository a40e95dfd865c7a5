"""Median host time of one call into `FusedTrainStep` until it returns (the
enqueue), from the benchmark's `chipbench.dispatch` span."""


def read(trace, spans, cell):
    return trace.span_median_ms("chipbench.dispatch")
