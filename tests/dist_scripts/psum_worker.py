"""2-process SPMD worker: cross-process global-array reduction.

Launched by tools/launch.py (the reference dist_sync_kvstore.py pattern:
same binary, N local processes, value-deterministic collectives).
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"

import jax

# N local processes cannot share a chip: CPU-only whatever was inherited
jax.config.update("jax_platforms", "cpu")

import numpy as onp

import mxnet_tpu as mx  # noqa: F401  (bootstraps jax.distributed from env)

from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def main():
    rank = jax.process_index()
    nproc = jax.process_count()
    assert nproc == 2, nproc
    devs = jax.devices()
    assert len(devs) == 4, devs  # 2 procs x 2 local cpu devices

    mesh = Mesh(onp.array(devs), ("dp",))
    local = onp.full((4, 2), rank + 1.0, onp.float32)
    x = jax.make_array_from_process_local_data(
        NamedSharding(mesh, P("dp")), local)
    total = jax.jit(lambda a: a.sum(),
                    out_shardings=NamedSharding(mesh, P()))(x)
    got = float(total.addressable_shards[0].data)
    # rank0 contributes 8 ones, rank1 8 twos -> 8 + 16
    assert got == 24.0, got
    print(f"rank {rank} OK {got}", flush=True)


if __name__ == "__main__":
    main()
