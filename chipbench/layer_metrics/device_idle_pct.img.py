"""1 - (union of the intervals in which any operation runs on device 0) /
traced window, in percent."""


def read(trace, spans, cell):
    return trace.idle_pct(0)
