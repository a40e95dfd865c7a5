"""Device contexts.

Reference: `include/mxnet/base.h:90` (``Context`` with kCPU/kGPU/kCPUPinned/
kCPUShared) and its python mirror `python/mxnet/context.py`.

TPU-native design: a ``Context`` names a JAX device (platform + ordinal).  The
reference's device kinds map as

==============  =========================================
reference       tpu-native
==============  =========================================
``cpu()``       jax cpu backend
``gpu(i)``      jax gpu backend, if present in the process
``tpu(i)``      jax tpu device *(new; the point of this build)*
``cpu_pinned``  cpu (XLA/PjRt stages host transfers itself)
``cpu_shared``  cpu (DataLoader workers return numpy; no
                fork+shm protocol is needed under PjRt)
==============  =========================================

Unlike the reference there is no per-context storage manager to talk to --
PjRt owns allocation (BFC arena) -- so a Context is a lightweight value type
used for placement (`ndarray.as_in_ctx`) and for the default-device stack.
"""
from __future__ import annotations

import threading

import jax

__all__ = [
    "Context",
    "cpu",
    "gpu",
    "tpu",
    "cpu_pinned",
    "cpu_shared",
    "num_gpus",
    "num_tpus",
    "current_context",
    "current_device",
    "default_device",
]

_thread_local = threading.local()


class Context:
    """A device context (reference `python/mxnet/context.py`)."""

    # Keep the reference's numeric device-type ids for checkpoint compat
    # (`include/mxnet/base.h:93-96`), and add kTPU.
    devtype2id = {"cpu": 1, "gpu": 2, "cpu_pinned": 3, "cpu_shared": 5, "tpu": 6}
    devid2type = {v: k for k, v in devtype2id.items()}

    _default_ctx = None  # class-level fallback, set lazily

    def __init__(self, device_type, device_id=0):
        if isinstance(device_type, Context):
            device_type, device_id = device_type.device_type, device_type.device_id
        if device_type not in self.devtype2id:
            raise ValueError(f"unknown device type {device_type!r}")
        self.device_type = device_type
        self.device_id = int(device_id)

    # -- identity ---------------------------------------------------------
    @property
    def device_typeid(self):
        return self.devtype2id[self.device_type]

    def __eq__(self, other):
        return (
            isinstance(other, Context)
            and self.device_type == other.device_type
            and self.device_id == other.device_id
        )

    def __hash__(self):
        return hash((self.device_type, self.device_id))

    def __repr__(self):
        return f"{self.device_type}({self.device_id})"

    __str__ = __repr__

    # -- jax mapping ------------------------------------------------------
    @property
    def _jax_platform(self):
        t = self.device_type
        if t in ("cpu", "cpu_pinned", "cpu_shared"):
            return "cpu"
        return t

    def jax_device(self):
        """The ``jax.Device`` this context denotes."""
        platform = self._jax_platform
        devices = _devices_for(platform)
        if not devices:
            raise MXNetContextError(
                f"no {platform} devices visible to this process "
                f"(jax backends: {_visible_platforms()})"
            )
        if self.device_id >= len(devices):
            raise MXNetContextError(
                f"{self} out of range: only {len(devices)} {platform} device(s)"
            )
        return devices[self.device_id]

    # -- scope ------------------------------------------------------------
    def __enter__(self):
        if not hasattr(_thread_local, "stack"):
            _thread_local.stack = []
        _thread_local.stack.append(self)
        return self

    def __exit__(self, *_exc):
        _thread_local.stack.pop()

    def empty_cache(self):
        """Best-effort analogue of `Storage::ReleaseAll`; PjRt pools internally."""
        # XLA's allocator reclaims on demand; nothing to do eagerly.
        return None


class MXNetContextError(RuntimeError):
    pass


def _visible_platforms():
    """Platforms this process has a backend for (``jax.devices()`` alone
    lists only the default one: on a TPU host ``cpu`` is there too)."""
    from jax.extend import backend as _backend

    return sorted(_backend.backends())


def _devices_for(platform):
    """This process's devices on ``platform``; ``[]`` when the process was
    not started with that platform (``JAX_PLATFORMS=cpu`` has no tpu).  A
    platform that is configured but fails to come up raises from
    ``jax.devices()`` here rather than reading as "no devices"."""
    if platform not in _visible_platforms():
        return []
    # multi-controller SPMD: a Context names a device of THIS process
    # (the reference's per-worker ctx semantics); global devices are
    # only ever addressed through shardings
    return jax.local_devices(backend=platform)


def cpu(device_id=0):
    return Context("cpu", device_id)


def cpu_pinned(device_id=0):
    return Context("cpu_pinned", device_id)


def cpu_shared(device_id=0):
    return Context("cpu_shared", device_id)


def gpu(device_id=0):
    return Context("gpu", device_id)


def tpu(device_id=0):
    return Context("tpu", device_id)


def num_gpus():
    return len(_devices_for("gpu"))


def num_tpus():
    return len(_devices_for("tpu"))


def _best_default():
    for platform in ("tpu", "gpu"):
        if _devices_for(platform):
            return Context(platform, 0)
    return Context("cpu", 0)


def on_tpu():
    """THE spelling of "this process computes on a TPU": the one test the
    Pallas kernels (compiled there, interpreted elsewhere) and the
    kernel-vs-XLA dispatch rules read."""
    return jax.default_backend() == "tpu"


def current_context():
    """The context on top of the with-stack, else the process default."""
    stack = getattr(_thread_local, "stack", None)
    if stack:
        return stack[-1]
    if Context._default_ctx is None:
        Context._default_ctx = _best_default()
    return Context._default_ctx


# Gluon 2 / np-API name for the same concept.
current_device = current_context
default_device = current_context
