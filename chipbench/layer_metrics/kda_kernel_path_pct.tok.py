"""Share of the program's linear-attention cores that were traced onto the
Pallas kernels: `mxtpu_linear_attention_lowerings{path="pallas_chunk"}` over
the counter's every path, one count a trace of `ops.linear_attention.kda`.
Under 100 a layer fell back to the XLA form in silence, which a slow kernel
would not show.  None where nothing was traced or the program has no counter.
It reads in the cells whose configuration has Kimi Delta Attention."""
COUNTER, KERNEL_PATH = "mxtpu_linear_attention_lowerings", "pallas_chunk"


def read(trace, spans, cell):
    from mxnet_tpu import telemetry
    counts = {dict(labels).get("path"): value
              for family, samples in telemetry.default_registry().collect()
              if family.name == COUNTER for _name, labels, value in samples}
    taken = sum(counts.values())
    return 100.0 * counts.get(KERNEL_PATH, 0) / taken if taken else None
