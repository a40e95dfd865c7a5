"""The one way the program times a region: :class:`span`.

A span is three things, all on one clock (``time.monotonic_ns``, the
flight recorder's ``mono_ns``):

* always, a ``jax.profiler.TraceAnnotation`` for its extent (outside a
  profiling session that is a flag test), so in every ``.xplane.pb`` —
  an operator's ``profiler.set_config(xla_trace_dir=...)`` or a
  benchmark's — the program's spans lie on the host plane beside the
  device's operations;
* at exit, ONE flight-recorder event that is the whole span: name,
  begin, end, the span that was open on this thread when it began, and
  the training step it belongs to (``observe.spans()`` reads them back;
  ``MXNET_BLACKBOX=0`` turns the record off and nothing else);
* while the profiler runs, a chrome-trace complete event (``ph:"X"``)
  under the span's category, so one ``profiler.dump()`` interleaves step
  phases, per-op dispatches, kvstore collectives and serve batches.

``step_phase`` and ``collective_span`` add their registry series
(histogram / counters) and are otherwise this span.  A span costs one
clock read at entry and one event at exit — per step, collective or
batch, never per op.

Set-up is on the same record from the process's first instruction:
``process_start_ns`` puts the kernel's start stamp on the spans' clock, and
``record_finished`` takes what was timed before this module could be
imported (``mxnet_tpu/__init__.py`` records ``process.before_import`` and
opens ``runtime.import`` with it).
"""
from __future__ import annotations

import itertools
import os
import threading
import time

import jax

from .. import observe as _observe
from .. import profiler as _profiler
from . import registry as _registry

__all__ = ["span", "record_finished", "process_start_ns", "step_phase",
           "collective_span", "mark_step"]

_ids = itertools.count(1)       # next() is atomic under the interpreter lock
_open = threading.local()       # .stack: the spans open on this thread


def _stack():
    try:
        return _open.stack
    except AttributeError:
        _open.stack = []
        return _open.stack


def _emit_chrome(name, cat, begin_ns, end_ns, args):
    if _profiler._running:
        _profiler._emit(name, cat, "X", begin_ns / 1e3, args=args,
                        dur=(end_ns - begin_ns) / 1e3)


class span:
    """Time the ``with`` block as ``name`` under category ``cat``.

    ``step`` is the training step the span belongs to; left out, a span
    takes its parent's.  A span shorter than ``floor_s`` seconds is left
    as ``cancel()`` leaves it: where a region runs by the thousand and
    only the long ones matter, the short ones stay in their parent's self
    time and take no slot in the record.  Further keywords are the span's
    arguments; code inside the block may add to ``.args`` what it learns
    there.  After exit ``begin_ns``, ``end_ns`` and ``seconds`` hold the
    reading."""

    __slots__ = ("name", "cat", "step", "floor_s", "args", "id", "parent",
                 "begin_ns", "end_ns", "_annotation")

    def __init__(self, name, cat="step_phase", step=None, floor_s=0.0,
                 **args):
        self.name = name
        self.cat = cat
        self.step = step
        self.floor_s = floor_s
        self.args = args

    def __enter__(self):
        stack = _stack()
        parent = stack[-1] if stack else None
        self.id = next(_ids)
        self.parent = parent.id if parent is not None else None
        if self.step is None and parent is not None:
            self.step = parent.step
        self._annotation = jax.profiler.TraceAnnotation(self.name)
        self._annotation.__enter__()
        stack.append(self)
        self.begin_ns = time.monotonic_ns()
        return self

    def __exit__(self, *exc):
        if self.floor_s:
            self.end_ns = time.monotonic_ns()
            if self.end_ns - self.begin_ns < self.floor_s * 1e9:
                self._close(*exc)
                return False
        self.end_ns = _observe.record_span(
            self.cat, self.name, self.begin_ns, id=self.id,
            parent=self.parent, step=self.step, **self.args)
        self._close(*exc)
        _emit_chrome(self.name, self.cat, self.begin_ns, self.end_ns,
                     self.args)
        return False

    def cancel(self):
        """Leave the span without recording it (a probe that found no work)."""
        self._close(None, None, None)

    def _close(self, *exc):
        stack = _stack()
        if stack and stack[-1] is self:
            stack.pop()
        elif self in stack:     # left out of order: keep the others' parents right
            stack.remove(self)
        self._annotation.__exit__(*exc)

    @property
    def seconds(self):
        return (self.end_ns - self.begin_ns) * 1e-9


def record_finished(name, cat, begin_ns, end_ns, **args):
    """Record a span that somebody else timed (jax reports a compile when it
    is over): same event, same parent and step rules, no annotation."""
    stack = _stack()
    parent_id, step = (stack[-1].id, stack[-1].step) if stack else (None, None)
    _observe.record_span(cat, name, begin_ns, end_ns, id=next(_ids),
                         parent=parent_id, step=step, **args)
    _emit_chrome(name, cat, begin_ns, end_ns, args)


def process_start_ns():
    """When this process started, on the spans' clock, or None where the
    system does not say: ``/proc/self/stat`` holds the start in ticks since
    boot, and ``CLOCK_BOOTTIME`` counts from there (to a tick, 10 ms)."""
    try:
        with open("/proc/self/stat") as f:
            # the fields after the command's name, which may hold spaces
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        age_ns = time.clock_gettime_ns(time.CLOCK_BOOTTIME) - \
            ticks * 1_000_000_000 // os.sysconf("SC_CLK_TCK")
    except (OSError, AttributeError, ValueError, IndexError):
        return None
    # a sandbox may count its ticks from another boot than its clock does
    return time.monotonic_ns() - age_ns if age_ns >= 0 else None


def _phase_histogram():
    return _registry.histogram(
        "mxtpu_trainer_step_phase_seconds",
        "Training step decomposition: data-wait / fwd / bwd / allreduce / "
        "optimizer (or fused-step for FusedTrainStep)",
        labelnames=("phase",))


def _steps_counter():
    return _registry.counter(
        "mxtpu_trainer_steps_total", "Optimizer steps taken")


class step_phase(span):
    """One phase of a training step: the span ``step/<phase>`` (or
    ``name``) plus an observation in the
    ``mxtpu_trainer_step_phase_seconds{phase=...}`` histogram."""

    __slots__ = ("phase",)

    def __init__(self, phase, name=None, step=None, **args):
        super().__init__(name or f"step/{phase}", step=step, **args)
        self.phase = phase

    def __exit__(self, *exc):
        super().__exit__(*exc)
        _phase_histogram().labels(phase=self.phase).observe(self.seconds)
        return False


def mark_step():
    """Count one optimizer step (`mxtpu_trainer_steps_total`)."""
    _steps_counter().inc()


def _collective_metrics():
    reg = _registry
    return (
        reg.counter("mxtpu_kvstore_collective_total",
                    "Cross-device collectives dispatched by the kvstore",
                    labelnames=("op",)),
        reg.counter("mxtpu_kvstore_collective_bytes_total",
                    "Payload bytes entering kvstore collectives",
                    labelnames=("op",)),
        reg.histogram("mxtpu_kvstore_collective_seconds",
                      "Host-side kvstore collective dispatch latency "
                      "(device time overlaps async; see the XLA trace for "
                      "on-wire timing)",
                      labelnames=("op",)),
        reg.counter("mxtpu_kvstore_collective_launches_total",
                    "XLA collective program launches dispatched by the "
                    "kvstore, across all ops (gradient bucketing collapses "
                    "many keys into one launch; per-key pushpull pays one "
                    "per parameter)"),
    )


class collective_span(span):
    """One kvstore collective: the span ``collective/<op>`` plus count,
    bytes and latency in the registry."""

    __slots__ = ()

    def __init__(self, op, nbytes=0):
        super().__init__(f"collective/{op}", cat="collective", op=op,
                         bytes=int(nbytes))

    def __enter__(self):
        op, nbytes = self.args["op"], self.args["bytes"]
        total, bytes_, _lat, launches = _collective_metrics()
        total.labels(op=op).inc()
        launches.inc()
        if nbytes:
            bytes_.labels(op=op).inc(nbytes)
        return super().__enter__()

    def __exit__(self, *exc):
        super().__exit__(*exc)
        _collective_metrics()[2].labels(op=self.args["op"]).observe(
            self.seconds)
        return False
