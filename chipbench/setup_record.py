"""Set-up as the program's own span record accounts for it, from the
process's start to the first dispatched step: what the `setup_*` readers
under `layer_metrics/` added with these spans share.

The program records its import (`process.before_import`, `runtime.import`,
`runtime.backend_start`), the eager forward that settles deferred shapes
(`block.settle_shapes`), what the first call into the step does once
(`fused_step.build`) and, beneath every `xla.trace`, one `block.trace` span
for each Gluon block whose `forward` took 10 ms or more to trace
(`docs/OBSERVABILITY.md` section 2).  A reading here is over the spans that
ended before the traced window began, like `program_record`'s, with two
differences.  It is `None` on a record without a `runtime.import` span: a
program from before these spans, whose `xla.*` spans alone would read as a
set-up with no import in it.  And it does not go silent because a long window
wrapped the ring: the recorder keeps the spans of set-up (those that began
before the first `fused_step.step` ended) where the wrap does not reach, and
says in `setup_dropped` whether that store is whole; the reading is then of
the store and of whatever of the warm-up the ring still holds.
"""
from __future__ import annotations

import collections

from chipbench import program_record
from chipbench.trace import subtract, total, union

IMPORT = ("process.before_import", "runtime.import", "runtime.backend_start")
PROCESS_START = "process.before_import"
BLOCK_TRACE = "block.trace"


def of(spans, lost, trace_spans):
    """The `program_record.Record` of a span list whose set-up is whole
    (`lost` 0) and holds the import, or None."""
    record = program_record.of(spans, lost, trace_spans)
    if record is None or record.before is None or \
            not any(s["name"] == "runtime.import" for s in record.before):
        return None
    return record


def load(trace_spans):
    """`of` this process's program against the trace's host spans."""
    from mxnet_tpu import observe
    read = getattr(observe, "spans", None)   # as `program_record.load`
    if read is None:
        return None
    spans = read()
    return of(spans, getattr(spans, "setup_dropped", spans.dropped), trace_spans)


def _interval(s):
    return s["begin_ns"], s["end_ns"]


def trace_self_s(before):
    """{class: seconds} of `block.trace` self time: each span less what its
    `block.trace` children cover, summed by the block's class."""
    traces = [s for s in before if s["name"] == BLOCK_TRACE]
    children = collections.defaultdict(list)
    for s in traces:
        children[s["parent"]].append(_interval(s))
    by_class = collections.Counter()
    for s in traces:
        own = subtract([_interval(s)], union(children[s["id"]]))
        by_class[s["args"].get("cls")] += total(own) / 1e9
    return dict(by_class)


def unaccounted_s(before):
    """Seconds between the process's start and the END of the first
    `fused_step.step` span that no span on record covers; None where the
    record holds no start (no `/proc`) or no step."""
    start = next((s["begin_ns"] for s in before if s["name"] == PROCESS_START), None)
    end = next((s["end_ns"] for s in before if s["name"] == program_record.STEP), None)
    if start is None or end is None:
        return None
    covered = union((max(b, start), min(e, end)) for b, e in map(_interval, before)
                    if b < end and e > start)
    return (end - start - total(covered)) / 1e9
