"""From the program's own span record to what the per-layer readers need.

The program under test times its regions with `mxnet_tpu.telemetry.span` and
keeps each as one event of its in-memory flight recorder, from process start;
`mxnet_tpu.observe.spans()` reads them back (`docs/OBSERVABILITY.md` section
2).  A reader runs in the run's own process after the window, so the record
is at hand, set-up included; the profiler trace is not (its host events reach
a reader only under the `chipbench.` prefix).  The two are joined by count:
the traced window made one `chipbench.dispatch` span per call into the step
(`spans`, on the trace's clock), and each call left one `fused_step.step`
span in the record (on `time.monotonic_ns`).  So the window's steps are the
last N `fused_step.step` spans, N the number of `chipbench.dispatch` spans.

A reading is `None`, and nothing raises, where it would be wrong or cannot be
made: a program from before the span record, the recorder switched off
(`MXNET_BLACKBOX=0`), fewer steps on record than the window dispatched.  The
recorder is a ring, so each reading asks for the spans it reads and no others:
a median over the window wants its span once under every step of the window;
a reading of set-up wants the record whole from process start, which it is
while the ring has overwritten nothing.  All durations are host time.
"""
from __future__ import annotations

import statistics

from chipbench.trace import total, union

DISPATCH = "chipbench.dispatch"
STEP = "fused_step.step"
XLA_STAGES = ("xla.trace", "xla.lower", "xla.compile")


class Record:
    """`window`: the `fused_step.step` spans of the traced window, in order.
    `inside`: they and every span whose parent is one of them.  `before`:
    every span that ended before the window began (set-up and warm-up), or
    None where the ring has lost events and set-up may be among them."""

    def __init__(self, spans, dropped, window):
        self.window = window
        ids = {s["id"] for s in window}
        self.inside = [s for s in spans if s["id"] in ids or s["parent"] in ids]
        begin = window[0]["begin_ns"]
        self.before = None if dropped else \
            [s for s in spans if s["end_ns"] <= begin]

    def median_ms(self, name):
        """Median duration of the spans `name` inside the window: the steps
        themselves (`fused_step.step`) or a part of them (`fused_step.prepare`).
        None unless the record holds one for every step of the window."""
        d = [s["end_ns"] - s["begin_ns"] for s in self.inside if s["name"] == name]
        return statistics.median(d) / 1e6 if len(d) == len(self.window) else None

    def covered_before_s(self, names):
        """Seconds before the window that some span named in `names` covers."""
        if self.before is None:
            return None
        return total(union((s["begin_ns"], s["end_ns"]) for s in self.before
                           if s["name"] in names)) / 1e9

    def count_before(self, name, **args):
        """Spans `name` before the window whose arguments hold `args`."""
        if self.before is None:
            return None
        return sum(s["name"] == name
                   and all(s["args"].get(k) == v for k, v in args.items())
                   for s in self.before)


def of(spans, dropped, trace_spans):
    """The `Record` of a span list as `observe.spans()` gives it, or None."""
    n = sum(name == DISPATCH for name, _start, _end in trace_spans)
    steps = [s for s in spans if s["name"] == STEP]
    if not n or len(steps) < n:
        return None
    return Record(spans, dropped, steps[-n:])


def load(trace_spans):
    """The `Record` of this process's program against the trace's host spans,
    or None (see the module's docstring)."""
    from mxnet_tpu import observe
    read = getattr(observe, "spans", None)   # the parent commit has none
    if read is None:
        return None
    spans = read()
    return of(spans, spans.dropped, trace_spans)
