"""Multi-host bootstrap shared by package import and `parallel.init_distributed`.

Depends only on os/jax so it can run before anything touches the XLA
backend (reference analogue: ps-lite's DMLC_* env bootstrap,
`src/kvstore/kvstore_dist.h:44`).
"""
from __future__ import annotations

import os
import warnings

# mxlint: disable-file=env-read-at-trace-time -- process bootstrap: every read happens once during package import / jax.distributed init, before any model code can trace

_ENV_VARS = ("JAX_COORDINATOR_ADDRESS", "JAX_NUM_PROCESSES",
             "JAX_PROCESS_ID")


def read_env():
    """Returns (coordinator_address, num_processes, process_id) from the
    launcher environment, or None if the env is absent or malformed (a
    malformed set warns rather than making the package unimportable)."""
    present = [v for v in _ENV_VARS if v in os.environ]
    if not present:
        return None
    if len(present) < len(_ENV_VARS):
        warnings.warn(
            f"incomplete multi-host environment: have {present}, need all "
            f"of {_ENV_VARS}; skipping jax.distributed bootstrap")
        return None
    try:
        return (os.environ["JAX_COORDINATOR_ADDRESS"],
                int(os.environ["JAX_NUM_PROCESSES"]),
                int(os.environ["JAX_PROCESS_ID"]))
    except ValueError:
        warnings.warn(
            "non-integer JAX_NUM_PROCESSES/JAX_PROCESS_ID; skipping "
            "jax.distributed bootstrap")
        return None


def init_from_env():
    """Call jax.distributed.initialize from the launcher env if present.
    Safe to call more than once; returns True if initialization ran."""
    spec = read_env()
    if spec is None:
        return False
    import jax

    try:
        jax.distributed.initialize(coordinator_address=spec[0],
                                   num_processes=spec[1],
                                   process_id=spec[2])
    except RuntimeError:
        return False  # backend already up (interactive import after use)
    # Eager (non-SPMD) ops must land on an ADDRESSABLE device: jax's
    # default is devices()[0], which on rank>0 belongs to process 0 and
    # raises "not fully addressable" on first use.  Pin the per-process
    # default to the first local device (the multi-controller contract).
    jax.config.update("jax_default_device", jax.local_devices()[0])
    _maybe_profile_rank(spec[2])
    return True


def _maybe_profile_rank(rank):
    """Remote-rank profiling (reference analogue: rank 0 switches a
    server's profiler over a kvstore command, `src/kvstore/
    kvstore_dist.h:99`).  In SPMD there is no server role, so the
    launcher carries the request instead: `tools/launch.py
    --profile-rank N [--profile-dir D]` sets MXNET_PROFILE_RANK /
    MXNET_PROFILE_DIR for every worker, and the matching rank starts the
    profiler here and dumps `D/profile_rank{N}.json` (chrome://tracing)
    at exit.  MXNET_PROFILE_RANK=-1 profiles every rank."""
    want = os.environ.get("MXNET_PROFILE_RANK")
    if want is None:
        return
    try:
        want = int(want)
    except ValueError:
        # same warn-don't-crash contract as read_env(): a malformed env
        # var must not make the package unimportable
        warnings.warn(f"MXNET_PROFILE_RANK={want!r} is not an integer; "
                      "profiling request ignored")
        return
    if want != -1 and want != rank:
        return
    import atexit

    from . import profiler
    out_dir = os.environ.get("MXNET_PROFILE_DIR", ".")
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, f"profile_rank{rank}.json")
    profiler.set_config(filename=path, profile_all=True)
    profiler.set_state("run")

    def _dump():
        try:
            profiler.set_state("stop")
            # write to the captured path directly: the training script may
            # have re-pointed the profiler's global filename at its own
            # trace, and the launcher-requested one must not clobber it
            with open(path, "w") as f:
                f.write(profiler.dumps(format="json"))
        except Exception as e:   # teardown must not fail the worker,
            warnings.warn(       # but silence would hide a lost trace
                f"profiler dump to {path} failed: {e}")
    atexit.register(_dump)
