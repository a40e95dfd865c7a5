"""Pipeline parallelism (GPipe-style) over a mesh axis.

The reference's only "pipeline" story is manual per-layer device placement
with automatic cross-device copies (`docs/.../model_parallel_lstm.md`,
`src/operator/cross_device_copy.cc`).  The TPU-native form: stack the
per-stage parameters along a leading axis sharded over the ``pp`` mesh
axis, and run microbatches through the stage ring with ``ppermute`` —
stage s computes microbatch m while stage s-1 computes m+1 (the classic
GPipe schedule expressed as one `lax.scan` under `shard_map`).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

__all__ = ["pipeline_apply"]


def _pipeline_local(params, x_mb, stage_fn, axis_name, num_microbatches):
    """Runs under shard_map: params (1, ...) is this stage's slice; x_mb is
    (M_local, B_mb, ...) microbatches, fully present only on stage 0
    (others receive zeros and ignore them)."""
    n_stages = lax.psum(1, axis_name)
    stage = lax.axis_index(axis_name)
    p = jax.tree_util.tree_map(lambda a: a[0], params)
    m = num_microbatches
    steps = m + n_stages - 1
    perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    def step(carry, t):
        outputs, cur = carry
        # stage 0 feeds microbatch t from the input queue; other stages
        # consume what arrived from the previous stage
        feed = jnp.where(t < m, t, 0)
        inp = jnp.where(stage == 0, x_mb[feed], cur)
        out = stage_fn(p, inp)
        # the last stage banks its result for microbatch t - (n_stages - 1)
        done_idx = t - (n_stages - 1)
        take = jnp.clip(done_idx, 0, m - 1)
        outputs = jnp.where(
            (stage == n_stages - 1) & (done_idx >= 0),
            outputs.at[take].set(out), outputs)
        nxt = lax.ppermute(out, axis_name, perm)
        return (outputs, nxt), None

    outputs0 = jnp.zeros((m,) + x_mb.shape[1:], x_mb.dtype)
    cur0 = jnp.zeros_like(x_mb[0])
    # fresh carries are device-invariant; mark them varying over the stage
    # axis so scan carry types match the per-stage outputs
    outputs0, cur0 = (lax.pcast(a, (axis_name,), to="varying")
                      for a in (outputs0, cur0))
    (outputs, _), _ = lax.scan(step, (outputs0, cur0), jnp.arange(steps))
    # broadcast the final outputs from the last stage to every stage so the
    # out_spec can be replicated
    outputs = lax.psum(
        jnp.where(stage == n_stages - 1, outputs, jnp.zeros_like(outputs)),
        axis_name)
    return outputs


def pipeline_apply(stage_fn, stage_params, x, mesh, axis_name="pp",
                   num_microbatches=None):
    """Apply a pipeline of identical stages to ``x``.

    stage_fn(params, x) -> y computes ONE stage (same signature per stage;
    y must have x's shape/dtype so it can flow to the next stage).
    stage_params: pytree whose leaves have a leading axis of size
    ``mesh.shape[axis_name]`` (one slice per stage), sharded over
    ``axis_name``.  x: (batch, ...) — split into ``num_microbatches``
    equal microbatches (defaults to the number of stages).

    Returns stage_{S-1}(...stage_0(x)) with GPipe microbatch overlap.
    """
    n_stages = mesh.shape[axis_name]
    m = num_microbatches or n_stages
    b = x.shape[0]
    if b % m:
        raise ValueError(f"batch {b} must divide into {m} microbatches")
    x_mb = x.reshape((m, b // m) + x.shape[1:])

    pspec = jax.tree_util.tree_map(
        lambda _a: P(axis_name), stage_params)
    fn = jax.shard_map(
        functools.partial(_pipeline_local, stage_fn=stage_fn,
                          axis_name=axis_name, num_microbatches=m),
        mesh=mesh,
        in_specs=(pspec, P()),
        out_specs=P(),
    )
    out = fn(stage_params, x_mb)
    return out.reshape((b,) + out.shape[2:])
