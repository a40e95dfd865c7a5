"""mxnet_tpu — a TPU-native deep learning framework with MXNet's capabilities.

Built from scratch on JAX/XLA (compute) for TPU hardware; see SURVEY.md for
the map from the reference (`sxjscience/mxnet`) to this design.  Import as::

    import mxnet_tpu as mx
    x = mx.np.ones((2, 3), ctx=mx.tpu())
"""
from __future__ import annotations

import time as _time

_IMPORT_BEGIN_NS = _time.monotonic_ns()   # the package's first line

import os as _os

# Lock-acquisition witness (tools/lockscan's runtime half): the factory
# patch must land BEFORE any package import creates a lock, so this is
# the first package code to run.  Reads os.environ directly — the env
# helpers themselves live behind imports that create locks.
if _os.environ.get("MXNET_LOCKSCAN_WITNESS", "") not in ("", "0"):
    from . import lockwitness as _lockwitness

    _lockwitness.install()

import jax as _jax

# Set-up is on the span record from here (docs/OBSERVABILITY.md section 2):
# `runtime.import` is this file, first line to last, and what the import
# does under a span of its own (`runtime.backend_start`, random.py) is its
# child.  The span machinery starts no backend.
from . import telemetry as _telemetry

_import_span = _telemetry.span("runtime.import", cat="setup").__enter__()
_import_span.begin_ns = _IMPORT_BEGIN_NS

# Multi-host bootstrap: when tools/launch.py (or a pod scheduler) provides
# coordination env vars, wire jax.distributed now — it must run before
# anything touches the XLA backend.
from . import _distributed

_distributed.init_from_env()

# Persistent compile cache, configured here and nowhere else.  jax reads
# JAX_COMPILATION_CACHE_DIR itself; without it the cache lives in the
# checkout, at a path that never moves between runs (a directory named
# after a pid, a time or a temp file would never hit).  A process held to
# the CPU (the tests) gets none: jaxlib 0.9.0's XLA:CPU loader logs a
# machine-feature mismatch at error level on every hit, and a CPU compile
# is not what costs minutes.
if not _os.environ.get("JAX_COMPILATION_CACHE_DIR") and \
        _jax.config.jax_platforms != "cpu":
    _jax.config.update(
        "jax_compilation_cache_dir",
        _os.path.join(_os.path.dirname(_os.path.dirname(
            _os.path.abspath(__file__))), ".jax_cache"))

# MXNet float32 ops compute in true float32 (CUDA/MKL kernels); XLA's
# "fastest" default would silently downcast matmul/conv inputs to bf16 on
# TPU.  Half-precision speed is opt-in via bf16 arrays / amp, as in the
# reference (float32 lowers to the MXU's 3-pass f32 path).
_jax.config.update("jax_default_matmul_precision", "float32")

from .base import MXNetError
from .context import (
    Context, cpu, gpu, tpu, cpu_pinned, cpu_shared,
    num_gpus, num_tpus, current_context, current_device,
)
from .ndarray.ndarray import NDArray, waitall
from . import ndarray
from . import ndarray as nd
from . import numpy  # noqa: F401
from . import numpy as np  # the mx.np namespace (shadows stdlib-style import on purpose)
from . import numpy_extension as npx
from . import autograd
from . import random
from . import symbol
from . import symbol as sym
from . import util
from .util import set_np, reset_np, is_np_array, use_np

from . import initializer
from . import init  # alias module
from . import optimizer
from . import lr_scheduler
from . import kvstore as kv
from . import kvstore
from . import io
from . import image
from . import contrib
from . import gluon
from . import models
from . import parallel
from . import amp
from . import profiler
from . import telemetry
from . import serve
from . import resilience
from .runtime import Features, feature_list
from . import callback
from . import model
from . import monitor
from . import rtc
from . import visualization
from . import visualization as viz
from . import test_utils
from . import attribute
from . import dlpack
from . import engine
from . import error
from . import libinfo
from . import log
from . import name
from . import operator
from . import env
from .libinfo import __version__

# honor the documented MXNET_* environment variables (env.py table)
env.apply()

# register NumPy __array_function__/__array_ufunc__ interop (reference
# `python/mxnet/numpy_dispatch_protocol.py:1`)
from . import numpy_dispatch  # noqa: E402  (needs np + NDArray above)

# legacy custom-op entry: mx.nd.Custom(data..., op_type="name")
ndarray.Custom = operator.invoke_custom  # (mx.nd is the same module)

_import_span.__exit__(None, None, None)
# the interpreter, `import jax` and whatever the host script did before it
# imported this package (a TPU runtime's start, if it asked for devices)
_process_start_ns = _telemetry.process_start_ns()
if _process_start_ns is not None:
    _telemetry.record_finished("process.before_import", "setup",
                               _process_start_ns, _IMPORT_BEGIN_NS)
del _import_span, _process_start_ns

__all__ = [
    "MXNetError", "Context", "cpu", "gpu", "tpu", "NDArray", "nd", "np",
    "npx", "autograd", "random", "gluon", "models", "optimizer", "kvstore", "kv",
    "initializer", "init", "lr_scheduler", "parallel", "amp", "profiler",
    "serve", "telemetry",
    "waitall", "current_context", "num_gpus", "num_tpus", "test_utils",
]
