"""Share of the program's grouped matmuls (the routed experts') that were
traced onto the Pallas kernels: `mxtpu_grouped_matmul_lowerings{path="pallas"}`
over the counter's every path, one count a trace of
`ops.grouped_matmul.grouped_matmul`.  Under 100 some fell back to
`lax.ragged_dot` in silence, which a slow kernel would not show.  None where
nothing was traced or the program has no counter."""
COUNTER, KERNEL_PATH = "mxtpu_grouped_matmul_lowerings", "pallas"


def read(trace, spans, cell):
    from mxnet_tpu import telemetry
    counts = {dict(labels).get("path"): value
              for family, samples in telemetry.default_registry().collect()
              if family.name == COUNTER for _name, labels, value in samples}
    taken = sum(counts.values())
    return 100.0 * counts.get(KERNEL_PATH, 0) / taken if taken else None
