"""Device mesh + sharding helpers.

The TPU-native replacement for the reference's device topology machinery
(`src/kvstore/gpu_topology.h` builds reduction trees from PCIe/NVLink
links).  On TPU the topology is the mesh: name the axes (`dp`, `tp`, `sp`,
`pp`, ...), annotate shardings, and XLA routes collectives over ICI.
"""
from __future__ import annotations

import logging
import re
import threading

import jax
import numpy as onp
from jax.sharding import Mesh, NamedSharding, PartitionSpec

__all__ = [
    "make_mesh", "current_mesh", "mesh_scope", "data_sharding",
    "replicated_sharding", "match_partition_rules", "shard_parameters",
    "constrain", "PartitionSpec", "RuleCoverage",
]

_state = threading.local()
_log = logging.getLogger(__name__)


def make_mesh(axes=None, devices=None):
    """Create a Mesh.  ``axes`` maps axis name -> size; sizes may use -1 once
    to absorb the remaining devices.  Default: 1-d data-parallel mesh over
    all devices: ``make_mesh({'dp': -1})``."""
    devices = devices if devices is not None else jax.devices()
    if axes is None:
        axes = {"dp": -1}
    names = list(axes)
    sizes = list(axes.values())
    n = len(devices)
    if sizes.count(-1) > 1:
        raise ValueError(
            f"mesh {dict(zip(names, sizes))}: at most one axis may be -1")
    if -1 in sizes:
        known = 1
        for sz in sizes:
            if sz != -1:
                known *= sz
        if known > n or n % known:
            raise ValueError(
                f"mesh {dict(zip(names, sizes))}: the explicit axes "
                f"({known}) must divide the device count ({n}) for -1 to "
                "absorb the remainder")
        sizes[sizes.index(-1)] = n // known
    total = 1
    for s in sizes:
        total *= s
    if total > n:
        raise ValueError(f"mesh {dict(zip(names, sizes))} needs {total} "
                         f"devices, have {n}")
    # a mesh may use a subset of devices (e.g. a 4-stage pipeline on an
    # 8-device host); take the first `total` — but say so, loudly: a
    # typo'd recipe (`dp2` on 8 chips) otherwise trains at quarter speed
    # with no visible symptom
    if total < n:
        _log.warning(
            "mesh %s uses %d of %d devices — %d device(s) idle; "
            "if unintended, size an axis -1 to absorb the remainder",
            dict(zip(names, sizes)), total, n, n - total)
    dev_array = onp.asarray(devices[:total]).reshape(sizes)
    return Mesh(dev_array, tuple(names))


def current_mesh():
    return getattr(_state, "mesh", None)


def current_batch_axes():
    """Mesh axes the leading (batch) dim is sharded over in the program
    being traced under :class:`mesh_scope`; ``()`` outside one."""
    return getattr(_state, "batch_axes", ())


class mesh_scope:
    """Trace-time declaration that the code inside becomes ONE program
    partitioned over ``mesh``, batch dim over ``batch_axes``
    (`FusedTrainStep` opens it around its forward+backward trace).  XLA
    cannot partition a Pallas (Mosaic) call by itself, so a kernel traced
    inside reads the scope and goes through :func:`shard_kernel`."""

    def __init__(self, mesh, batch_axes=()):
        self.mesh = mesh
        self.batch_axes = tuple(batch_axes)

    def __enter__(self):
        self._prev = (current_mesh(), current_batch_axes())
        _state.mesh, _state.batch_axes = self.mesh, self.batch_axes
        return self.mesh

    def __exit__(self, *_exc):
        _state.mesh, _state.batch_axes = self._prev


def axes_size(mesh, names):
    """Number of shards a dim split over mesh axes ``names`` has."""
    size = 1
    for name in names:
        size *= mesh.shape[name]
    return size


def shard_kernel(fn, in_specs, out_specs):
    """``fn`` as one launch per device of the current mesh.  ``check_vma``
    is off because a pallas_call's outputs declare no variance over mesh
    axes; an ``out_specs`` that leaves an axis out is therefore a promise
    that ``fn`` made the value equal along it (e.g. by a ``psum``)."""
    return jax.shard_map(fn, mesh=current_mesh(), in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def data_sharding(mesh, axis_name="dp"):
    """Shard the leading (batch) axis over the given mesh axis."""
    return NamedSharding(mesh, PartitionSpec(axis_name))


def replicated_sharding(mesh):
    return NamedSharding(mesh, PartitionSpec())


class RuleCoverage(dict):
    """The ``name -> PartitionSpec`` mapping from
    :func:`match_partition_rules`, with the audit trail attached:

    * ``matched``: name -> the regex pattern that decided its spec
      (first match wins);
    * ``replicated``: names of non-scalar params that fell through every
      rule and defaulted to replicated — the set a tp/pp recipe audit
      cares about (a fallen-through 4 GB embedding silently replicates
      onto every chip);
    * ``scalars``: names short-circuited to replicated because sharding
      a scalar/size-1 array is meaningless.

    Plain-dict callers are unaffected: this IS the dict they had.
    """

    def __init__(self):
        super().__init__()
        self.matched = {}
        self.replicated = []
        self.scalars = []

    def summary(self):
        return (f"{len(self.matched)} rule-matched, "
                f"{len(self.replicated)} fell through to replicated, "
                f"{len(self.scalars)} scalar")


def match_partition_rules(rules, names_to_shapes, strict=False):
    """Map parameter names to PartitionSpecs by regex rules.

    ``rules``: list of (pattern, PartitionSpec); first match wins; scalars
    and unmatched params are replicated.  Returns a :class:`RuleCoverage`
    (a dict subclass) recording which rule matched each param and which
    fell through.  ``strict=True`` raises ``ValueError`` naming every
    non-scalar param no rule matched — the fmengine-style audit a tp/pp
    recipe runs so an uncovered tensor cannot silently replicate.
    """
    out = RuleCoverage()
    for name, shape in names_to_shapes.items():
        if len(shape) == 0 or int(onp.prod(shape)) == 1:
            out[name] = PartitionSpec()
            out.scalars.append(name)
            continue
        spec = None
        for pattern, ps in rules:
            if re.search(pattern, name):
                spec = ps
                out.matched[name] = pattern
                break
        if spec is None:
            spec = PartitionSpec()
            out.replicated.append(name)
        out[name] = spec
    if strict and out.replicated:
        raise ValueError(
            "partition rule not found for param(s): "
            + ", ".join(sorted(out.replicated))
            + " — every non-scalar parameter must match a rule under a "
            "strict (tp/pp) recipe; add a block partition_rules() or a "
            "user override, or pass strict=False to replicate them")
    return out


def _transfer_metrics():
    from .. import telemetry as _tm

    return (
        _tm.counter("mxtpu_mesh_transfer_total",
                    "Host->mesh placements via parallel.global_put",
                    labelnames=("kind",)),
        _tm.counter("mxtpu_mesh_transfer_bytes_total",
                    "Bytes placed onto the mesh via parallel.global_put",
                    labelnames=("kind",)),
    )


def global_put(value, sharding):
    """Place host/single-device data under a (possibly multi-process)
    sharding.  For a fully-addressable mesh this is ``jax.device_put``;
    across processes each process supplies its addressable shards from
    the (identical-everywhere) full value — the SPMD data contract of
    `jax.make_array_from_callback`.

    Publishes count/bytes into the telemetry registry — per-step input
    placement dominates DCN traffic on multi-host meshes, so it is the
    first series to read when a pod step slows down."""
    total, bytes_ = _transfer_metrics()
    nbytes = getattr(value, "nbytes", 0)
    if sharding.is_fully_addressable:
        total.labels(kind="device_put").inc()
        if nbytes:
            bytes_.labels(kind="device_put").inc(int(nbytes))
        return jax.device_put(value, sharding)
    host = onp.asarray(value)
    total.labels(kind="callback").inc()
    bytes_.labels(kind="callback").inc(int(host.nbytes))
    return jax.make_array_from_callback(
        host.shape, sharding, lambda idx: host[idx])


def shard_put(value, sharding, pool=None):
    """Place host data under ``sharding`` by putting each addressable
    shard DIRECTLY on its device: one ``jax.device_put`` of the shard's
    slice per device, assembled with
    `jax.make_array_from_single_device_arrays`.

    Contrast with :func:`global_put`, which for a fully-addressable mesh
    ships the whole value once and lets jax lay it out — for a batch
    destined to be dp-sharded that is replicate-then-slice: dp x the
    wire bytes and a device-side slice.  Here the wire carries each byte
    exactly once (the per-shard puts overlap when ``pool`` is given),
    which is the input-feed law the prefetcher needs.

    Falls back to :func:`global_put` when the shape does not tile under
    the sharding (indivisible leading dim, scalar).  The
    ``kind="shard_put"`` bytes series counts what the wire actually
    carried — sum of per-shard bytes, so a tiled placement reads 1x the
    host bytes and a replicated one reads num_devices x; a bench
    asserting zero host-side replication diffs this series against batch
    bytes.
    """
    host = onp.asarray(value)
    try:
        idx_map = sharding.addressable_devices_indices_map(host.shape)
    except (ValueError, TypeError):
        # shape does not tile (e.g. a ragged last batch): replicate on
        # the same mesh — correctness over the wire saving for the odd
        # batch out
        mesh = getattr(sharding, "mesh", None)
        if mesh is None:
            raise
        return global_put(value, NamedSharding(mesh, PartitionSpec()))
    total, bytes_ = _transfer_metrics()
    items = list(idx_map.items())
    if pool is not None and len(items) > 1:
        shards = list(pool.map(
            lambda di: jax.device_put(host[di[1]], di[0]), items))
    else:
        shards = [jax.device_put(host[idx], d) for d, idx in items]
    total.labels(kind="shard_put").inc()
    # sum the bytes each put actually carried: a tiled sharding counts
    # host.nbytes exactly once, a replicated placement (rank-0 / leading
    # dim that does not divide the mesh) shows num_devices x — the
    # telemetry must expose replication, not assume it away
    bytes_.labels(kind="shard_put").inc(
        sum(int(s.nbytes) for s in shards))
    return jax.make_array_from_single_device_arrays(
        host.shape, sharding, shards)


def shard_parameters(params, mesh, rules=None, strict=False):
    """Place Gluon Parameters onto the mesh.

    ``params``: dict name -> Parameter.  Each parameter's array is re-placed
    with a NamedSharding; replicated unless a rule matches.  This is the
    TPU analogue of `kvstore.broadcast` of initial params
    (`python/mxnet/gluon/trainer.py:164-174`).  Works across processes
    (multi-host mesh): every process holds identical initial values (same
    seed), so `global_put` hands each its local shards.

    The returned :class:`RuleCoverage` says which rule placed each param;
    the coverage summary is logged and the fell-through-to-replicated
    count published as the ``mxtpu_recipe_params_replicated_total`` gauge
    (a nonzero value under a tp/pp recipe is the first thing to check
    when per-chip memory doesn't drop).  ``strict=True`` raises instead
    — see :func:`match_partition_rules`.
    """
    from .. import telemetry as _tm

    specs = match_partition_rules(
        rules or [], {k: p.shape for k, p in params.items()}, strict=strict)
    for name, p in params.items():
        sharding = NamedSharding(mesh, specs[name])
        arr = p.data()
        arr._rebind(global_put(arr._data, sharding))
    _log.info("shard_parameters: placed %d param(s) on mesh %s — %s",
              len(specs), dict(mesh.shape), specs.summary())
    if specs.replicated:
        _log.info("shard_parameters: replicated fall-throughs: %s",
                  ", ".join(sorted(specs.replicated)))
    _tm.gauge(
        "mxtpu_recipe_params_replicated_total",
        "Non-scalar params the last shard_parameters call replicated "
        "because no partition rule matched them",
    ).set(len(specs.replicated))
    return specs


def constrain(x, mesh, spec):
    """`with_sharding_constraint` over NDArrays (usable inside hybridized
    forwards to steer XLA's sharding propagation)."""
    from ..ndarray.ndarray import NDArray
    from ..ops.invoke import invoke

    sharding = NamedSharding(mesh, spec) if not isinstance(
        spec, NamedSharding) else spec

    def f(d):
        return jax.lax.with_sharding_constraint(d, sharding)

    return invoke(f, (x,), name="sharding_constraint")


def init_distributed(coordinator_address=None, num_processes=None,
                     process_id=None):
    """Initialize multi-host JAX from explicit args or the environment set
    by `tools/launch.py` (JAX_COORDINATOR_ADDRESS / JAX_NUM_PROCESSES /
    JAX_PROCESS_ID).

    The reference analogue is ps-lite's DMLC_* env bootstrap
    (`src/kvstore/kvstore_dist.h`); here every process is a peer and the
    coordination service at process 0 takes the scheduler's role.  On a
    real TPU pod slice, call with no arguments outside a launcher — the
    TPU runtime supplies the topology.
    """
    import jax

    if coordinator_address is None and num_processes is None and \
            process_id is None:
        from .._distributed import init_from_env
        init_from_env()
        return
    jax.distributed.initialize(coordinator_address=coordinator_address,
                               num_processes=num_processes,
                               process_id=process_id)
