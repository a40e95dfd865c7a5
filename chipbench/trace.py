"""From a profiler trace (`.xplane.pb`) to what the per-layer metrics read:
busy intervals, the operation table, exposed collectives, and idle gaps set
against the benchmark's own host spans.  All times are seconds on the
trace's clock.  `Trace` takes plain lists, so the arithmetic is tested on a
hand-made one (tests/chipbench_tests/trace_fixture.json)."""
from __future__ import annotations

import collections
import re
import statistics

MOSAIC = "tpu_custom_call"
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "chipbench."
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "collective-permute",
               "all-to-all")


def union(intervals):
    """Sorted, disjoint intervals covering the same points as `intervals`."""
    merged = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return [tuple(m) for m in merged]


def total(intervals):
    return sum(end - start for start, end in intervals)


def subtract(a, b):
    """The parts of the disjoint sorted intervals `a` that `b` does not cover."""
    out = []
    for start, end in a:
        for bs, be in b:
            if be <= start or bs >= end:
                continue
            if bs > start:
                out.append((start, bs))
            start = max(start, be)
        if start < end:
            out.append((start, end))
    return out


def is_collective(label):
    return label.startswith(COLLECTIVES)


def is_custom_call(label):
    return label.startswith(MOSAIC)


def label_of(hlo):
    """A short label for one device operation from the HLO text the trace
    names it by, `%name.7 = type opcode(operands), attributes`: the opcode for
    a collective, `tpu_custom_call:<name>` for a Mosaic (Pallas) kernel, else
    the instruction's name without its number (`convert_bitcast_fusion` says
    more than `fusion`); then the result's shape without layouts."""
    m = re.match(r"%?([^ ]+?)(?:\.\d+)? = (.*?) ([a-z][a-z0-9\-]*)\(", hlo)
    if not m:
        return hlo[:80]
    name, shape, opcode = m.groups()
    if MOSAIC in hlo:
        name = MOSAIC + ":" + name
    elif opcode.startswith(COLLECTIVES):
        name = opcode
    return (name + " " + re.sub(r"\{[^}]*\}", "", shape))[:120]


class Trace:
    """`ops[chip]` is [(label, start, end)] of one device's operations (see
    `label_of`) and `spans` is [(name, start, end)] of the benchmark's host
    spans.  The window runs from device 0's first operation to its last."""

    def __init__(self, ops, spans):
        self.ops, self.spans = ops, sorted(spans, key=lambda s: s[1])
        self.window = (min(o[1] for o in ops[0]), max(o[2] for o in ops[0]))
        self.window_s = self.window[1] - self.window[0]
        self.busy_s = sum(total(self.busy(c)) for c in range(len(ops))) / len(ops)

    def busy(self, chip=0, keep=lambda name: True):
        return union((s, e) for name, s, e in self.ops[chip] if keep(name))

    def idle_pct(self, chip=0):
        return 100.0 * (1.0 - total(self.busy(chip)) / self.window_s)

    def exposed_collective_pct(self, chip=0):
        """Share of the window in which a collective runs and nothing else does."""
        alone = subtract(self.busy(chip, is_collective),
                         self.busy(chip, lambda n: not is_collective(n)))
        return 100.0 * total(alone) / self.window_s

    def span_median_ms(self, name):
        d = [e - s for n, s, e in self.spans if n == name]
        return 1e3 * statistics.median(d) if d else None

    def gaps(self, chip=0, n=5):
        """[(span the host was mostly in, seconds)] of the `n` longest idle gaps."""
        idle = sorted(subtract([self.window], self.busy(chip)), key=lambda g: g[0] - g[1])
        out = []
        for start, end in idle[:n]:
            overlap = collections.Counter()
            for name, s, e in self.spans:
                if s < end and e > start:
                    overlap[name] += min(e, end) - max(s, start)
            out.append((overlap.most_common(1)[0][0] if overlap else "outside spans",
                        end - start))
        return out

    def breakdown(self, n_ops=10, n_gaps=5):
        by_name = collections.Counter()
        for name, s, e in self.ops[0]:
            by_name[name] += e - s
        return {"device_ops": [list(x) for x in by_name.most_common(n_ops)],
                "idle_gaps": [list(g) for g in self.gaps(0, n_gaps)]}


def load(path, chips):
    """The `Trace` of the first `chips` TPU planes of an `.xplane.pb`."""
    from jax.profiler import ProfileData
    ops, spans, labels = {}, [], {}   # 20 steps repeat the same few thousand texts

    def label(hlo):
        if hlo not in labels:
            labels[hlo] = label_of(hlo)
        return labels[hlo]

    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:TPU:"):
            chip = int(plane.name.rsplit(":", 1)[1])
            for line in plane.lines:
                if line.name == OPS_LINE and chip < chips:
                    ops[chip] = [(label(e.name), e.start_ns * 1e-9, e.end_ns * 1e-9)
                                 for e in line.events]
        elif plane.name.startswith("/host:"):
            spans += [(e.name, e.start_ns * 1e-9, e.end_ns * 1e-9)
                      for line in plane.lines for e in line.events
                      if e.name.startswith(SPAN_PREFIX)]
    return Trace([ops[c] for c in sorted(ops)], spans)
