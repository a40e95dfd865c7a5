"""Random state.

Reference: `python/mxnet/random.py` (global + per-context seeding over the
engine's mshadow PRNG resources, `src/resource.cc:93`).

TPU-native design: JAX randomness is functional (explicit keys).  To keep the
reference's *stateful* API (`mx.random.seed`, samplers that just work), the
module keeps a key stream: a root key advanced per draw.  Under ``hybridize``
tracing, a traced per-call key is pushed onto the stream stack so compiled
programs get fresh randomness every call instead of a baked-in constant (the
trace-time analogue of the reference handing each op an engine RNG resource).
"""
from __future__ import annotations

import threading

import jax
import jax.numpy as jnp
from jax._src import xla_bridge as _xla_bridge

from . import telemetry as _telemetry

__all__ = ["seed", "new_key", "advance", "key_stream_scope", "uniform",
           "normal", "randint", "host_rng"]


class _KeyState(threading.local):
    def __init__(self):
        self.root = jax.random.key(0)
        self.counter = 0
        self.stack = []  # traced KeyStream scopes
        self.host = None  # lazy host-side Generator (image aug scalars)
        self.host_seeded_with = None


# the root key is the package's first touch of the XLA backend: importing
# the package starts the runtime, unless the host script already had
with _telemetry.span(
        "runtime.backend_start", cat="setup",
        already_up=_xla_bridge.backends_are_initialized()) as _started:
    _state = _KeyState()
    _started.args.update(platform=jax.default_backend(),
                         devices=jax.device_count())
del _started

# process-wide host seed so worker threads created AFTER mx.random.seed()
# still derive deterministic streams (each thread gets its own Generator,
# keyed by the global seed + a spawn index — numpy Generators are not
# thread-safe to share).  _host_seed = (generation, seed) so re-seeding
# with the same value still resets every thread's stream.
_host_seed = [(0, None)]
_host_spawn = [0]
_host_lock = threading.Lock()


def host_rng():
    """Host-side numpy Generator for data-independent dispatch-time draws
    (image augmentation factors, crop offsets) — deterministic per thread
    once ``seed()`` has set the process-wide host seed (reference:
    per-call mshadow host RNG, `src/resource.cc:93`).  Threads receive
    independent streams spawned from the seed in thread-creation order."""
    import numpy as onp
    if _state.host is None or _state.host_seeded_with != _host_seed[0]:
        gen, seed_val = _host_seed[0]
        with _host_lock:
            idx = _host_spawn[0]
            _host_spawn[0] += 1
        if seed_val is None:
            _state.host = onp.random.default_rng()
        else:
            _state.host = onp.random.default_rng(
                onp.random.SeedSequence(seed_val).spawn(idx + 1)[idx])
        _state.host_seeded_with = _host_seed[0]
    return _state.host


class KeyStream:
    """Deterministic stream of subkeys split from a base key."""

    def __init__(self, base_key):
        self.base = base_key
        self.n = 0

    def next(self):
        self.n += 1
        return jax.random.fold_in(self.base, self.n)


def seed(seed_state, ctx="all"):
    """Reference: `python/mxnet/random.py` `seed()`; ctx kept for API compat
    (XLA PRNG is device-independent so per-context seeding is a no-op)."""
    import numpy as onp
    _state.root = jax.random.key(int(seed_state))
    _state.counter = 0
    _host_seed[0] = (_host_seed[0][0] + 1, int(seed_state))
    _host_spawn[0] = 0
    _state.host = onp.random.default_rng(int(seed_state))
    _state.host_seeded_with = _host_seed[0]


def new_key():
    """Next PRNG key: from the innermost traced stream if one is active
    (hybridize), else by advancing the global stateful stream."""
    if _state.stack:
        return _state.stack[-1].next()
    _state.counter += 1
    return jax.random.fold_in(_state.root, _state.counter)


def advance(n):
    """Skip the global stream forward by ``n`` draws without dispatching
    anything — the next `new_key()` returns what the (n+1)-th call would
    have.  The divergence auto-rollback uses this: after restoring a
    checkpoint the supervisor jumps the stream PAST the poisoned window,
    so the re-run samples a different trajectory instead of
    deterministically reproducing the spike (checkpoint restore already
    put root/counter back to the snapshot values)."""
    _state.counter += int(n)


def root_and_counter():
    """Advance the global stream exactly like `new_key()` but return
    (root_key, counter) WITHOUT dispatching the fold_in — callers that
    run a jitted program every step (FusedTrainStep) fold inside the
    program instead, saving a per-step device dispatch.
    `fold_in(root, counter)` in-program yields the
    identical key `new_key()` would have produced."""
    _state.counter += 1
    return _state.root, _state.counter


class key_stream_scope:
    """Push a traced base key for the duration of a trace (used by
    HybridBlock's compiled path)."""

    def __init__(self, base_key):
        self.stream = KeyStream(base_key)

    def __enter__(self):
        _state.stack.append(self.stream)
        return self.stream

    def __exit__(self, *_exc):
        _state.stack.pop()


# Stateful sampler shims (the full zoo lives in mxnet_tpu.numpy.random).
def uniform(low=0, high=1, shape=(), dtype=None, ctx=None, out=None, device=None):
    from .numpy import random as nprandom
    return nprandom.uniform(low, high, size=shape, dtype=dtype, ctx=ctx or device, out=out)


def normal(loc=0, scale=1, shape=(), dtype=None, ctx=None, out=None, device=None):
    from .numpy import random as nprandom
    return nprandom.normal(loc, scale, size=shape, dtype=dtype, ctx=ctx or device, out=out)


def randint(low, high=None, shape=(), dtype=None, ctx=None, out=None, device=None):
    from .numpy import random as nprandom
    return nprandom.randint(low, high, size=shape, dtype=dtype, ctx=ctx or device, out=out)
