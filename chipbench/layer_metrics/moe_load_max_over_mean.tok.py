"""The most loaded held expert over the mean held expert, in the layer where
that ratio is largest, on the last step: from the `expert_load` counters the
step writes as auxiliary state (`mxnet_tpu.parallel.moe.expert_loads`).  None
where the program has no such counter or nothing was routed."""
from chipbench import layer_work


def read(trace, spans, cell):
    ratios = [max(l["rows"]) * len(l["rows"]) / sum(l["rows"])
              for l in layer_work.expert_loads() if sum(l["rows"])]
    return max(ratios) if ratios else None
