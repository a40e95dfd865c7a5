"""Profiler.

Reference: `src/profiler/profiler.h:251` + `python/mxnet/profiler.py` —
chrome://tracing JSON dumps, aggregate stat tables, user Domains/Tasks/
Frames/Events/Counters wired into every engine OprBlock.

TPU-native design: compiled-program timing comes from the XLA/jax profiler
(TensorBoard-compatible traces, `jax.profiler.start_trace`); this module
keeps the reference's python API surface and additionally records host-side
scopes into a chrome-trace JSON so `dump()` behaves as before.  The two can
be combined: `set_config(profile_all=True, xla_trace_dir=...)`.
"""
from __future__ import annotations

import json
import threading
import time

import jax

__all__ = [
    "set_config", "set_state", "state", "dump", "dumps", "pause", "resume",
    "Domain", "Task", "Frame", "Event", "Counter", "Marker", "scope",
]

_lock = threading.Lock()
_config = {"filename": "profile.json", "xla_trace_dir": None}
_running = False
_events = []


def _now_us():
    """Microseconds on the flight recorder's clock (`time.monotonic_ns`), the
    one `telemetry.span` stamps its chrome events from."""
    return time.monotonic_ns() / 1e3


def set_config(**kwargs):
    _config.update(kwargs)


def set_state(state_name="stop", profile_process="worker"):
    global _running
    if state_name == "run":
        _running = True
        if _config.get("xla_trace_dir"):
            jax.profiler.start_trace(_config["xla_trace_dir"])
    elif state_name == "stop":
        if _running and _config.get("xla_trace_dir"):
            jax.profiler.stop_trace()
        _running = False
    else:
        raise ValueError("state must be 'run' or 'stop'")


def state():
    return "run" if _running else "stop"


def pause(profile_process="worker"):
    global _running
    _running = False


def resume(profile_process="worker"):
    global _running
    _running = True


def _emit(name, cat, ph, ts, args=None, dur=None):
    ev = {"name": name, "cat": cat, "ph": ph, "ts": ts, "pid": 0,
          "tid": threading.get_ident() % 100000}
    if args:
        ev["args"] = args
    if dur is not None:
        ev["dur"] = dur
    with _lock:
        _events.append(ev)


def record_op(name, ts, dur):
    """Per-operator event hook (called by `ops.invoke` while profiling —
    the analogue of the engine's ProfileOperator wrapping,
    `src/engine/threaded_engine.h:83`)."""
    _emit(name, "operator", "X", ts, dur=dur)


def dumps(reset=False, format="table"):
    """format='json': chrome://tracing events; format='table': aggregate
    per-name statistics (reference `AggregateStats`,
    `src/profiler/aggregate_stats.cc`)."""
    with _lock:
        events = list(_events)
        if reset:
            _events.clear()
    if format == "json":
        return json.dumps({"traceEvents": events}, indent=1)
    agg = {}
    for ev in events:
        if ev.get("ph") != "X":
            continue
        name = ev["name"]
        cnt, tot, mx_ = agg.get(name, (0, 0.0, 0.0))
        dur = ev.get("dur", 0.0)
        agg[name] = (cnt + 1, tot + dur, max(mx_, dur))
    lines = [f"{'Name':<40}{'Count':>8}{'Total(us)':>14}{'Avg(us)':>12}"
             f"{'Max(us)':>12}", "-" * 86]
    for name, (cnt, tot, mx_) in sorted(agg.items(),
                                        key=lambda kv: -kv[1][1]):
        lines.append(f"{name[:39]:<40}{cnt:>8}{tot:>14.1f}"
                     f"{tot / cnt:>12.1f}{mx_:>12.1f}")
    return "\n".join(lines)


def dump(finished=True, profile_process="worker"):
    """Write the chrome://tracing JSON to the configured filename.

    ``finished=True`` (default) also stops the profiler *before* the event
    snapshot and resets the buffer with it — one atomic
    ``dumps(reset=True)``, so no event recorded mid-dump can be dropped
    unrecorded and the next run starts clean.  ``finished=False`` leaves
    the profiler running and the buffer intact (periodic flushing)."""
    if finished and _running:
        set_state("stop")
    payload = dumps(format="json", reset=finished)
    with open(_config["filename"], "w") as f:
        f.write(payload)


class Domain:
    def __init__(self, name):
        self.name = name

    def new_task(self, name):
        return Task(self, name)

    def new_frame(self, name):
        return Frame(self, name)

    def new_event(self, name):
        return Event(name)

    def new_counter(self, name, value=None):
        return Counter(self, name, value)

    def new_marker(self, name):
        return Marker(self, name)


class _Span:
    _ph_cat = "task"

    def __init__(self, domain, name):
        self.domain = domain
        self.name = name
        self._start = None

    def start(self):
        self._start = _now_us()

    def stop(self):
        if self._start is not None and _running:
            _emit(self.name, self._ph_cat, "X", self._start,
                  dur=_now_us() - self._start)
        self._start = None

    def __enter__(self):
        self.start()
        return self

    def __exit__(self, *_exc):
        self.stop()


class Task(_Span):
    _ph_cat = "task"


class Frame(_Span):
    _ph_cat = "frame"


class Event(_Span):
    def __init__(self, name):
        super().__init__(None, name)
    _ph_cat = "event"


class Counter:
    def __init__(self, domain, name, value=None):
        self.domain = domain
        self.name = name
        # increments are read-modify-write and arrive from concurrent
        # serve threads — unprotected they lose updates
        self._vlock = threading.Lock()
        self._value = 0
        if value is not None:
            self.set_value(value)

    @property
    def value(self):
        """Current counter value (readable with the profiler stopped —
        serving `stats()` polls this)."""
        return self._value

    def set_value(self, value):
        with self._vlock:
            self._value = value
        self._sample(value)

    def increment(self, delta=1):
        with self._vlock:
            self._value += delta
            value = self._value
        self._sample(value)

    def decrement(self, delta=1):
        self.increment(-delta)

    def _sample(self, value):
        if _running:
            _emit(self.name, "counter", "C", _now_us(),
                  args={self.name: value})

    def __iadd__(self, v):
        self.increment(v)
        return self

    def __isub__(self, v):
        self.decrement(v)
        return self


class Marker:
    def __init__(self, domain, name):
        self.domain = domain
        self.name = name

    def mark(self, scope="process"):
        if _running:
            _emit(self.name, "marker", "i", _now_us())


class scope:
    """Context manager timing a host-side region (also forwards to the jax
    profiler's TraceAnnotation so regions show in XLA traces)."""

    def __init__(self, name):
        self.name = name
        self._span = Task(None, name)
        self._jax_ctx = None

    def __enter__(self):
        # enter the jax annotation BEFORE starting the host span: if the
        # TraceAnnotation constructor/enter raises, no host state has
        # changed yet, so nothing dangles
        jax_ctx = jax.profiler.TraceAnnotation(self.name)
        jax_ctx.__enter__()
        self._jax_ctx = jax_ctx
        self._span.start()
        return self

    def __exit__(self, *exc):
        # stop the span first (mirror of enter order), then close the jax
        # annotation exactly once; tolerate exit-after-failed-enter
        self._span.stop()
        jax_ctx, self._jax_ctx = self._jax_ctx, None
        if jax_ctx is not None:
            jax_ctx.__exit__(*exc)


def dump_memory_profile(path="memory.pprof"):
    """Write a device-memory snapshot in pprof format (the GPU memory
    profiler analogue, reference `src/profiler/storage_profiler.h:131`;
    on TPU the allocator is PjRt's, introspected via jax.profiler)."""
    import jax.profiler as _jp

    with open(path, "wb") as f:
        f.write(_jp.device_memory_profile())
    return path
