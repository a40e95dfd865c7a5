"""Unified telemetry: metrics registry, step-trace spans, retrace watchdog.

The cross-cutting observability layer (docs/OBSERVABILITY.md):

* :mod:`.registry` — thread-safe counters/gauges/histograms with labels,
  Prometheus-text and JSON exposition.  ``serve`` endpoints, the kvstore
  collectives, the Gluon ``Trainer`` step phases, and (while profiling)
  ``ops.invoke`` all publish into the default registry;
* :mod:`.spans` — ``span``, the one way the program times a region: a
  ``jax.profiler.TraceAnnotation``, one flight-recorder event that holds
  the whole span (``observe.spans()``), and, while the profiler runs, a
  chrome-trace event, so one ``profiler.dump()`` interleaves step phases,
  op events, collective timings, and serve batch dispatches;
* :mod:`.watchdog` — XLA compile counters and ``xla.*`` spans via
  ``jax.monitoring`` plus per-jitted-function retrace detection with
  steady-state warnings.

Everything is off the hot path by default: spans and registry
publications happen per step / collective / serve batch, never per op
(per-op Python work happens only while the profiler runs).
"""
from .registry import (
    MetricsRegistry, Counter, Gauge, Histogram, DEFAULT_BUCKETS,
    default_registry, counter, gauge, histogram,
    export_prometheus, export_json,
)
from .spans import (span, record_finished, process_start_ns, step_phase,
                    collective_span, mark_step)
from .watchdog import (
    RetraceWatchdog, watchdog, watch_jit, install_compile_listener,
    trace_span,
)

__all__ = [
    "MetricsRegistry", "Counter", "Gauge", "Histogram", "DEFAULT_BUCKETS",
    "default_registry", "counter", "gauge", "histogram",
    "export_prometheus", "export_json",
    "span", "record_finished", "process_start_ns", "step_phase",
    "collective_span", "mark_step",
    "RetraceWatchdog", "watchdog", "watch_jit", "install_compile_listener",
    "trace_span",
]

# the listener only fires on compiles — safe to wire unconditionally
install_compile_listener()
