"""``mxnet_tpu.observe`` — pod-wide flight recorder + postmortem dumps.

The black box behind every chaos gate: a bounded per-host ring of
structured events (see ``flightrec``), atomic per-host dumps on terminal
errors/signals/demand, and the ``tools/blackbox`` analyzer that merges
N per-host dumps into one clock-skew-corrected pod timeline with a
root-cause verdict (docs/OBSERVABILITY.md "Black box / postmortem").
"""
from ..lockwitness import LockOrderViolation  # noqa: F401  (observability surface)
from .flightrec import (FlightRecorder, SCHEMA_VERSION, SpanList, configure,
                        default_recorder, dump, enabled, events,
                        install_signal_handlers, record, record_span, reset,
                        set_generation, set_rank, set_step, snapshot, spans)

__all__ = ["FlightRecorder", "LockOrderViolation", "SCHEMA_VERSION",
           "SpanList", "configure", "default_recorder", "dump", "enabled",
           "events", "install_signal_handlers", "record", "record_span",
           "reset", "set_generation", "set_rank", "set_step", "snapshot",
           "spans"]
