"""Share of the program's mHC stream mixes that were traced onto the Pallas
kernels: `mxtpu_hyperconnection_lowerings{path="pallas"}` over the counter's
every path, one count a trace of a `HyperConnection`'s mixes.  Under 100 a
sublayer fell back to the XLA form in silence, which a slow step would not
show.  None where nothing was traced or the program has no counter.  It reads
in the cells whose configuration has mHC's residual streams."""
COUNTER, KERNEL_PATH = "mxtpu_hyperconnection_lowerings", "pallas"


def read(trace, spans, cell):
    from mxnet_tpu import telemetry
    counts = {dict(labels).get("path"): value
              for family, samples in telemetry.default_registry().collect()
              if family.name == COUNTER for _name, labels, value in samples}
    taken = sum(counts.values())
    return 100.0 * counts.get(KERNEL_PATH, 0) / taken if taken else None
