"""Share of the traced window, on device 0, in which a collective operation
runs and no other operation does; None on one chip."""


def read(trace, spans, cell):
    return trace.exposed_collective_pct(0) if len(trace.ops) > 1 else None
