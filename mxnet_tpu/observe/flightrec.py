"""Pod flight recorder: a bounded, always-on ring buffer of structured
events behind every existing emitter.

The recorder is a *sink*, not an instrumentation pass: the taps live in
the subsystems that already observe the interesting transitions —
``telemetry.span`` (every timed region of the program is ONE event that
holds the whole span — begin, end, parent, step — so the ring is also the
program's span record from process start; ``spans()`` reads it back, and
the spans of set-up, those that began before the first ``fused_step.step``
span ended, are also held where the ring's wrap does not reach),
``resilience.faultline``
(injections), ``resilience.sentinel`` (straggler demotions, divergence
trips), ``resilience.elastic`` (reshards, rollbacks, preempt resumes),
``resilience.checkpoint`` (save/restore outcomes), ``kvstore.tpu_ici``
(heartbeat stamps and liveness observations), and ``serve.fleet``
(replica death, ejection, reroutes, failover).  Each tap is one
``record()`` call: two clock reads, a payload dict, and a lock held only
for an index bump plus a slot write — cheap enough to leave on in
production (the ci.sh ``blackbox`` stage gates the overhead at <1% of
step time).

Events are ``(mono_ns, wall_ns, rank, generation, category, name,
payload)``.  ``mono_ns`` orders events within a host; ``wall_ns`` is the
cross-host axis that ``tools/blackbox`` skew-corrects from the heartbeat
stamps each dump also carries.  ``generation`` is the elastic world
generation at record time, bumped by the supervisor on re-shard.

Dumps are atomic per-host JSON files (tmp + fsync + rename — the same
discipline as ``resilience.checkpoint``), keyed by (host, generation,
step), written next to the checkpoint step dirs (``<root>/blackbox``),
into ``MXNET_BLACKBOX_DIR``, or wherever ``configure(root=...)`` pointed.
Triggered on ``DeadNodeError`` / ``DegradedNodeError`` /
``DivergenceError`` / ``abort_to_checkpoint``, on SIGTERM/SIGINT
(faulthandler-style: dump, then chain to the previous handler), and on
demand via ``observe.dump()``.

Knobs (documented in ``mxnet_tpu/env.py``): ``MXNET_BLACKBOX=0``
disables recording entirely, ``MXNET_BLACKBOX_EVENTS`` sizes the ring
(default 4096), ``MXNET_BLACKBOX_DIR`` fixes the dump directory.
"""
from __future__ import annotations

import _thread
import json
import os
import signal
import threading
import time

from .. import env as _env

__all__ = ["FlightRecorder", "record", "record_span", "spans", "SpanList",
           "events", "snapshot", "dump",
           "reset", "configure", "enabled", "set_rank", "set_generation",
           "set_step", "install_signal_handlers", "SCHEMA_VERSION"]

SCHEMA_VERSION = 1

# what `record_span` and `telemetry.span` put in a span event's payload
# beside the span's own arguments
_SPAN_KEYS = frozenset(("begin_ns", "end_ns", "seconds", "id", "parent", "step"))


# the span that ends set-up: a span that began before the first of these
# ended is kept beside the ring, at most `_SETUP_SPANS` of them
_SETUP_ENDS_WITH = "fused_step.step"
_SETUP_SPANS = 4096


class SpanList(list):
    """What `spans()` returns: the spans; ``dropped``, how many events the
    ring lost that nothing else holds; ``setup_dropped``, how many spans of
    set-up found their store full (0: the set-up record is whole)."""

    def __init__(self, dropped=0, setup_dropped=0):
        super().__init__()
        self.dropped = dropped
        self.setup_dropped = setup_dropped


class FlightRecorder:
    """Bounded ring of structured events; oldest events are overwritten.

    ``record()`` is the only hot call: clocks and the payload tuple are
    built outside the lock, which protects exactly an index bump and a
    slot write.
    """

    def __init__(self, capacity=None, enabled=None):
        self._lock = threading.Lock()
        self._cap = int(capacity) if capacity else _env.blackbox_events()
        self._enabled = (_env.blackbox_enabled()
                         if enabled is None else bool(enabled))
        self._buf = [None] * self._cap
        self._n = 0
        self._clear_setup()
        self._rank = 0
        self._generation = 0
        self._step = None
        self._root = None

    # -- hot path ---------------------------------------------------------

    def record(self, category, name, **payload):
        """Append one event; drops silently when disabled."""
        if not self._enabled:
            return
        self._append((time.monotonic_ns(), time.time_ns(), self._rank,
                      self._generation, category, name, payload or None))

    def _append(self, ev, setup=False):
        with self._lock:
            if setup:
                if len(self._setup) < _SETUP_SPANS:
                    self._setup.append((self._n, ev))
                else:
                    self._setup_dropped += 1
            self._buf[self._n % self._cap] = ev
            self._n += 1

    def _clear_setup(self):
        # [(the event's number in the ring's count, the event)], and the end
        # of the first `_SETUP_ENDS_WITH` span (None: set-up is not over)
        self._setup = []
        self._setup_dropped = 0
        self._setup_end = None

    def record_span(self, category, name, begin_ns, end_ns=None, **payload):
        """Append one event that is a whole span and return its end on the
        ``mono_ns`` clock.  A span that closes now (``end_ns`` None) ends at
        the stamp this event gets; one reported after the fact (an XLA
        compile) carries its own ``end_ns``.  The end is returned with the
        recorder off too: the caller's histogram and chrome event need it."""
        now = time.monotonic_ns()
        end = now if end_ns is None else end_ns
        if self._enabled:
            payload["begin_ns"] = begin_ns
            payload["seconds"] = (end - begin_ns) * 1e-9
            if end_ns is not None:
                payload["end_ns"] = end_ns
            setup_end = self._setup_end
            self._append((now, time.time_ns(), self._rank, self._generation,
                          category, name, payload),
                         setup=setup_end is None or begin_ns < setup_end)
            if setup_end is None and name == _SETUP_ENDS_WITH:
                self._setup_end = end
        return end

    # -- context ----------------------------------------------------------

    def set_rank(self, rank):
        self._rank = int(rank)

    def set_generation(self, generation):
        self._generation = int(generation)

    def set_step(self, step):
        self._step = None if step is None else int(step)

    def set_root(self, root):
        """Default dump directory parent (the checkpoint root)."""
        if root is not None:
            self._root = os.fspath(root)

    @property
    def enabled(self):
        return self._enabled

    def set_enabled(self, enabled):
        self._enabled = bool(enabled)

    # -- snapshot / dump --------------------------------------------------

    def _ordered(self):
        n, cap = self._n, self._cap
        if n <= cap:
            return self._buf[:n]
        i = n % cap
        return self._buf[i:] + self._buf[:i]

    def events(self):
        """Events oldest-first (at most ``capacity``)."""
        with self._lock:
            return self._ordered()

    def spans(self, name=None):
        """The spans of set-up (kept beside the ring: those that began
        before the first ``fused_step.step`` span ended), then the other span
        events still in the ring, oldest first, as dicts with ``name``,
        ``cat``, ``begin_ns``, ``end_ns`` (both on the ``mono_ns`` clock),
        ``id``, ``parent``, ``step`` and ``args``.  ``.dropped`` on the list
        is the number of events of any kind that the ring has overwritten
        and set-up's store does not hold, so 0 means the record is whole
        since process start; ``.setup_dropped`` the spans of set-up that
        found the store full, so 0 means set-up's is, however long the run."""
        with self._lock:
            evs, lost = self._ordered(), max(0, self._n - self._cap)
            kept, setup_dropped = list(self._setup), self._setup_dropped
        held = {number for number, _ev in kept}
        evs = [ev for _number, ev in kept] + \
            [ev for number, ev in enumerate(evs, lost) if number not in held]
        out = SpanList(lost - sum(number < lost for number in held),
                       setup_dropped)
        for mono, _wall, _rank, _gen, cat, ev_name, payload in evs:
            if not payload or "begin_ns" not in payload or \
                    (name is not None and ev_name != name):
                continue
            args = {k: v for k, v in payload.items() if k not in _SPAN_KEYS}
            out.append({"name": ev_name, "cat": cat,
                        "begin_ns": payload["begin_ns"],
                        "end_ns": payload.get("end_ns", mono),
                        "id": payload.get("id"),
                        "parent": payload.get("parent"),
                        "step": payload.get("step"), "args": args})
        return out

    def snapshot(self, reason="on_demand"):
        """The dump payload as a dict, without touching disk."""
        evs = self.events()
        return {
            "schema": SCHEMA_VERSION,
            "host": self._rank,
            "generation": self._generation,
            "step": self._step,
            "reason": reason,
            "capacity": self._cap,
            "recorded": self._n,
            "dropped": max(0, self._n - self._cap),
            "dumped_mono_ns": time.monotonic_ns(),
            "dumped_wall_ns": time.time_ns(),
            "events": [list(e) for e in evs],
        }

    def _dump_dir(self, root=None):
        env_dir = _env.blackbox_dir()
        if env_dir:
            return env_dir
        base = root if root is not None else self._root
        if base is not None:
            return os.path.join(os.fspath(base), "blackbox")
        return os.path.join(".", "blackbox")

    def dump(self, reason="on_demand", root=None, path=None):
        """Atomically write the per-host dump (tmp + fsync + rename, the
        checkpoint discipline) and return its path, or None when the
        recorder is disabled."""
        if not self._enabled:
            return None
        snap = self.snapshot(reason=reason)
        if path is None:
            d = self._dump_dir(root)
            os.makedirs(d, exist_ok=True)
            step = snap["step"] if snap["step"] is not None else 0
            path = os.path.join(
                d, "blackbox-host%05d-gen%03d-step%010d.json"
                % (snap["host"], snap["generation"], step))
        tmp = "%s.tmp-%d" % (path, os.getpid())
        with open(tmp, "w") as f:
            json.dump(snap, f, default=str)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path)
        try:
            dirfd = os.open(os.path.dirname(path) or ".", os.O_RDONLY)
            try:
                os.fsync(dirfd)
            finally:
                os.close(dirfd)
        except OSError:  # mxlint: disable=swallowed-exception -- dir fsync is best-effort on exotic filesystems; the rename is already durable enough for a postmortem artifact
            pass
        return path

    def reset(self, capacity=None, enabled=None):
        """Clear the ring and re-read the env knobs (test/gate hook)."""
        with self._lock:
            self._cap = (int(capacity) if capacity
                         else _env.blackbox_events())
            self._enabled = (_env.blackbox_enabled()
                             if enabled is None else bool(enabled))
            self._buf = [None] * self._cap
            self._n = 0
            self._clear_setup()
            self._generation = 0
            self._step = None


_recorder = FlightRecorder()


def default_recorder():
    return _recorder


def record(category, name, **payload):
    _recorder.record(category, name, **payload)


def record_span(category, name, begin_ns, end_ns=None, **payload):
    return _recorder.record_span(category, name, begin_ns, end_ns, **payload)


def spans(name=None):
    return _recorder.spans(name)


def events():
    return _recorder.events()


def snapshot(reason="on_demand"):
    return _recorder.snapshot(reason=reason)


def dump(reason="on_demand", root=None, path=None):
    return _recorder.dump(reason=reason, root=root, path=path)


def reset(capacity=None, enabled=None):
    _recorder.reset(capacity=capacity, enabled=enabled)


def enabled():
    return _recorder.enabled


def configure(root=None, capacity=None, enabled=None):
    """Point the default recorder at a dump root and/or resize it."""
    if root is not None:
        _recorder.set_root(root)
    if capacity is not None or enabled is not None:
        with _recorder._lock:
            if capacity is not None:
                _recorder._cap = int(capacity)
                _recorder._buf = [None] * _recorder._cap
                _recorder._n = 0
                _recorder._clear_setup()
            if enabled is not None:
                _recorder._enabled = bool(enabled)


def set_rank(rank):
    _recorder.set_rank(rank)


def set_generation(generation):
    _recorder.set_generation(generation)


def set_step(step):
    _recorder.set_step(step)


_signals_installed = False


def _signal_dumper(read_fd, prev_handlers):
    """Deferred dump worker.  The handler only ``os.write``s the signum
    to a pre-opened pipe (async-signal-safe); this daemon thread does
    the lock-taking work — record + dump + chain — that a handler must
    never do (lockscan signal-unsafe: the signal may have landed on the
    thread that holds the recorder lock)."""
    while True:
        try:
            data = os.read(read_fd, 1)
        except OSError:
            return
        if not data:
            return
        signum = int(data[0])
        _recorder.record("terminal", "signal", signum=signum)
        try:
            _recorder.dump(reason="signal%d" % signum)
        except OSError:  # mxlint: disable=swallowed-exception -- a failed postmortem dump must never mask the signal itself; the chain below still runs
            pass
        prev = prev_handlers.get(signum)
        if prev is signal.default_int_handler:
            # the stock Ctrl-C disposition: KeyboardInterrupt belongs on
            # the main thread, not on this worker
            _thread.interrupt_main()
        elif callable(prev):
            prev(signum, None)
        elif prev == signal.SIG_DFL:
            # emulate the default terminate disposition —
            # signal.signal() may only be called from the main thread
            os._exit(128 + signum)


def install_signal_handlers():
    """Dump the flight record on SIGTERM/SIGINT, then chain to the
    previous handler (faulthandler-style).  Self-pipe shape: the
    installed handler only writes the signum to a pre-opened pipe fd
    and returns; a daemon worker performs the actual record + dump
    off-handler.  Idempotent; silently a no-op off the main thread or
    when recording is disabled."""
    global _signals_installed
    if _signals_installed or not _recorder.enabled:
        return False
    rfd = wfd = None
    try:
        prev = {signal.SIGTERM: signal.getsignal(signal.SIGTERM),
                signal.SIGINT: signal.getsignal(signal.SIGINT)}
        rfd, wfd = os.pipe()

        def _handler(signum, frame):
            os.write(wfd, bytes([int(signum)]))

        signal.signal(signal.SIGTERM, _handler)
        signal.signal(signal.SIGINT, _handler)
    except ValueError:  # mxlint: disable=swallowed-exception -- signal.signal raises off the main thread; recording works fine without the dump-on-signal path there
        if rfd is not None:
            os.close(rfd)
            os.close(wfd)
        return False
    # mxlint: disable=daemon-thread-no-shutdown -- true process-lifetime singleton: the dumper must outlive everything joinable to catch a terminal signal, and install is once-per-process
    threading.Thread(target=_signal_dumper, args=(rfd, prev),
                     name="flightrec-signal-dumper", daemon=True).start()
    _signals_installed = True
    return True
