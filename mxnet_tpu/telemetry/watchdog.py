"""Retrace/compile watchdog.

On TPU the dominant "why is this step 1000x slower" bug class is
shape-driven retracing: a jitted function silently recompiles because an
input shape, dtype, or static argument changed (the serve bucket grid
exists exactly to prevent it).  The reference engine made recompiles
visible through the profiler; here they are first-class metrics:

* a process-wide ``jax.monitoring`` listener counts every XLA compile
  stage (trace / lower / backend-compile) with durations —
  ``mxtpu_xla_compile_total{stage}`` / ``mxtpu_xla_compile_seconds`` —
  and records each as a span ``xla.trace`` / ``xla.lower`` /
  ``xla.compile`` with jax's ``fun_name`` (``telemetry.spans``), so the
  record says which function compiled, when, and under which step (a
  trace under 10 ms, as every eager op's is, is counted and not recorded;
  ``trace_span`` puts the Gluon blocks' ``block.trace`` spans beneath a
  trace under the same floor);
* per-function attribution rides the jit trace-cache size:
  ``RetraceWatchdog.observe(fn, name)`` (called by ``HybridBlock`` and
  ``FusedTrainStep`` after each dispatch, or via the ``watch_jit``
  wrapper for user functions) bumps ``mxtpu_jit_retrace_total{fn}``
  whenever the cache grew beyond the first compile, and logs a WARNING
  when the growth happens after the configurable steady-state call count
  (`steady_after`, env ``MXNET_TELEMETRY_STEADY_STEPS``) — by then every
  legitimate signature should have been seen.
"""
from __future__ import annotations

import logging
import os
import threading
import time
import weakref

from . import registry as _registry
from . import spans as _spans

__all__ = ["RetraceWatchdog", "watchdog", "watch_jit",
           "install_compile_listener", "trace_span"]

_log = logging.getLogger("mxnet_tpu.telemetry")

# jax.monitoring event names (jax._src.dispatch) -> exposition stage label
_EVENT_STAGES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile",
}

_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"

# a trace shorter than this takes no slot in the span record: eager ops
# retrace by the hundred, each well under a millisecond (in a BERT-base
# set-up 938 of the ring's 1,349 events), and the counters below keep their
# number and their sum
_TRACE_SPAN_FLOOR_S = 0.01


def trace_span(name, **args):
    """A span over Python work done while jax traces (a Gluon block's
    ``forward`` on tracers: ``block.trace``), in the ``xla.*`` spans'
    category and under the floor of ``xla.trace``: a model's trace runs
    thousands of them, and a shorter one is not recorded."""
    return _spans.span(name, cat="compile", floor_s=_TRACE_SPAN_FLOOR_S,
                       **args)


# compiles are seconds-scale events; default sub-ms buckets would be noise
_COMPILE_BUCKETS = (0.01, 0.05, 0.1, 0.5, 1.0, 5.0, 10.0, 30.0, 60.0)

_listener_lock = threading.Lock()
_listener_installed = False


def install_compile_listener(registry=None):
    """Register the process-wide ``jax.monitoring`` listeners that feed the
    XLA compile counters and record the ``xla.*`` spans.  Idempotent;
    installed automatically on ``mxnet_tpu.telemetry`` import.  Returns
    True on first install."""
    global _listener_installed
    with _listener_lock:
        if _listener_installed:
            return False
        _listener_installed = True
    reg = registry or _registry.default_registry()
    total = reg.counter(
        "mxtpu_xla_compile_total",
        "XLA compilations by stage (trace=abstract eval, lower=StableHLO "
        "emission, compile=backend codegen)", labelnames=("stage",))
    seconds = reg.histogram(
        "mxtpu_xla_compile_seconds", "Time spent in each XLA compile stage",
        labelnames=("stage",), buckets=_COMPILE_BUCKETS)

    # per compiling thread: `.hit`, a persistent-cache hit that jax reported
    # inside the backend-compile event that is still open; `.traces`, the
    # trace events open (a step's trace holds thousands of inner jit traces:
    # the counters see them all, the record only the outermost, and of
    # those only the ones of `_TRACE_SPAN_FLOOR_S` or more)
    local = threading.local()

    def _on_event(event, **_kw):
        if event == _CACHE_HIT_EVENT:
            local.hit = True

    def _on_scalar(event, _start, **_kw):
        if event == _TRACE_EVENT:
            local.traces = getattr(local, "traces", 0) + 1

    def _on_time_span(event, start, end, fun_name=None, **_kw):
        """jax stamps a stage with ``time.time()`` at both ends; the span is
        put on the record's clock by the two clocks' offset now."""
        stage = _EVENT_STAGES.get(event)
        if stage is None:
            return
        total.labels(stage=stage).inc()
        seconds.labels(stage=stage).observe(end - start)
        args = {"fun_name": fun_name}
        if stage == "trace":
            local.traces = getattr(local, "traces", 1) - 1
            if local.traces > 0 or end - start < _TRACE_SPAN_FLOOR_S:
                return
        elif stage == "compile":
            args["cache_hit"] = getattr(local, "hit", False)
            local.hit = False
        to_mono = time.monotonic_ns() - time.time_ns()
        _spans.record_finished(
            "xla." + stage, "compile", int(start * 1e9) + to_mono,
            int(end * 1e9) + to_mono, **args)

    import jax.monitoring as _jm
    _jm.register_event_listener(_on_event)
    _jm.register_scalar_listener(_on_scalar)
    _jm.register_event_time_span_listener(_on_time_span)
    return True


class _Tracked:
    __slots__ = ("calls", "cache_size", "ref")

    def __init__(self):
        self.calls = 0
        self.cache_size = None
        self.ref = None


class RetraceWatchdog:
    """Per-function recompile tracking over jit trace-cache sizes.

    Parameters
    ----------
    steady_after : int
        Calls after which a function is considered steady-state: a cache
        miss (new trace) past this count logs a WARNING naming the
        function.  Default from ``MXNET_TELEMETRY_STEADY_STEPS``, else 2
        (call 1 legitimately compiles; warmup variants get one more).
    registry : MetricsRegistry
        Where ``mxtpu_jit_retrace_total{fn}`` lives (default registry).
    """

    def __init__(self, steady_after=None, registry=None, logger=None):
        if steady_after is None:
            # mxlint: disable=env-read-at-trace-time -- host-side read at watchdog construction; per-instance override is the documented contract
            steady_after = int(
                os.environ.get("MXNET_TELEMETRY_STEADY_STEPS", "2"))
        self.steady_after = int(steady_after)
        reg = registry or _registry.default_registry()
        self._retraces = reg.counter(
            "mxtpu_jit_retrace_total",
            "Trace-cache growth of watched jitted functions beyond their "
            "first compile (nonzero in steady state = shape-driven "
            "retracing)", labelnames=("fn",))
        self._lock = threading.Lock()
        self._tracked = {}

    def retrace_count(self, name):
        return self._retraces.labels(fn=name).value

    def observe(self, fn, name, detail=None, scope_root=None):
        """Record one completed call of ``fn`` (a ``jax.jit`` callable).
        Compares the trace-cache size against the last call; growth beyond
        the first compile counts as a retrace, and growth after
        ``steady_after`` calls additionally warns.  Returns the growth,
        with the first compile counted (0 where ``fn`` cannot be tracked).

        ``scope_root`` is the entry point's name-stack root (the Gluon
        block name whose `jax.named_scope` wraps the traced program) —
        included in the WARNING so a retrace storm names the layer
        hierarchy that recompiled, not just a cache size."""
        try:
            size = fn._cache_size()
        except Exception:  # mxlint: disable=swallowed-exception -- not a PjitFunction (mocks, AOT wrappers): nothing to track, observing is optional
            return 0
        with self._lock:
            ent = self._tracked.get(id(fn))
            if ent is None:
                ent = self._tracked[id(fn)] = _Tracked()
                key = id(fn)
                try:
                    # drop the entry when fn dies so a recycled id() can't
                    # inherit stale call counts (and we never pin the
                    # compiled program or its captured params)
                    ent.ref = weakref.ref(
                        fn, lambda _r, _k=key: self._tracked.pop(_k, None))
                except TypeError:
                    ent.ref = None
            ent.calls += 1
            calls, prev = ent.calls, ent.cache_size
            ent.cache_size = size
        if prev is None:
            return size     # the first compile: growth, but no retrace
        if size <= prev:
            return 0
        self._retraces.labels(fn=name).inc(size - prev)
        if calls > self.steady_after:
            extras = "".join(
                [f" [name-stack root '{scope_root}']" if scope_root else "",
                 f" [{detail}]" if detail else ""])
            _log.warning(
                "retrace watchdog: %s recompiled at call %d (trace cache "
                "%d -> %d)%s — a steady-state recompile usually means an "
                "input shape/dtype or static argument is drifting "
                "(unbucketed batch dim?); each one stalls the step for the "
                "full XLA compile", name, calls, prev, size, extras)
        return size - prev

    def watch(self, fn, name=None):
        """Wrap a jitted callable so every call is observed.  Note: the
        wrapper is not a ``jax.stages.Wrapped``, so pass the *unwrapped*
        function anywhere that special-cases jit objects (e.g. the tape's
        deferred-vjp fast path) and call ``observe`` yourself instead."""
        return _WatchedJit(self, fn,
                           name or getattr(fn, "__name__", "jit_fn"))


class _WatchedJit:
    def __init__(self, wd, fn, name):
        self._wd = wd
        self._fn = fn
        self._name = name

    def __call__(self, *args, **kwargs):
        out = self._fn(*args, **kwargs)
        self._wd.observe(self._fn, self._name)
        return out

    def __getattr__(self, attr):
        return getattr(self._fn, attr)


_default_watchdog = None
_default_watchdog_lock = threading.Lock()


def watchdog():
    """The process-wide watchdog instance (shared by HybridBlock,
    FusedTrainStep, and ``watch_jit``)."""
    global _default_watchdog
    if _default_watchdog is None:
        with _default_watchdog_lock:
            if _default_watchdog is None:
                _default_watchdog = RetraceWatchdog()
    return _default_watchdog


def watch_jit(fn, name=None):
    """Wrap ``fn`` (jitted) so the default watchdog sees every call."""
    return watchdog().watch(fn, name)
