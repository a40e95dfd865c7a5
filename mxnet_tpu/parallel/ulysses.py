"""All-to-all (Ulysses-style) sequence parallelism.

The second canonical long-context scheme next to ring attention
(`parallel/ring_attention.py`): instead of rotating K/V blocks around
the ICI ring, ONE ``all_to_all`` re-shards activations from
sequence-sharded to head-sharded, full (unsharded) attention runs
locally per head group, and a second ``all_to_all`` re-shards back
(Jacobs et al., "DeepSpeed Ulysses", 2023; see PAPERS.md).  The
reference has no sequence parallelism at all (SURVEY.md §5.7).

Trade-off vs ring: Ulysses moves 2 all-to-alls of the activations and
needs ``num_heads % sp == 0``, but runs attention as one dense block
per device (best MXU utilization, any attention kernel drops in); ring
keeps heads whole and overlaps transfer with compute but runs T/sp-size
blocks.  Pick per topology; both ride the same ``sp`` mesh axis.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

__all__ = ["ulysses_attention", "ulysses_attention_local"]


def _dense_attention(q, k, v, causal, scale):
    b, h, t, d = q.shape
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if causal:
        mask = jnp.tril(jnp.ones((t, k.shape[2]), bool))
        s = jnp.where(mask, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32)) \
        .astype(q.dtype)


def ulysses_attention_local(q, k, v, axis_name, causal=False, scale=None):
    """Per-shard body (under shard_map).  q/k/v: (B, H, T_local, D) with
    the FULL head set and the local sequence block; internally re-shards
    to (B, H/sp, T, D), attends, and re-shards back."""
    if scale is None:
        scale = q.shape[-1] ** -0.5
    # seq-sharded -> head-sharded: split heads (axis 1) across the group,
    # concatenate sequence (axis 2)
    qh = lax.all_to_all(q, axis_name, split_axis=1, concat_axis=2,
                        tiled=True)
    kh = lax.all_to_all(k, axis_name, split_axis=1, concat_axis=2,
                        tiled=True)
    vh = lax.all_to_all(v, axis_name, split_axis=1, concat_axis=2,
                        tiled=True)
    out = _dense_attention(qh, kh, vh, causal, scale)
    # head-sharded -> seq-sharded
    return lax.all_to_all(out, axis_name, split_axis=2, concat_axis=1,
                          tiled=True)


def ulysses_attention(q, k, v, mesh, axis_name="sp", causal=False,
                      scale=None, batch_axis=None):
    """Sharded entry point, same contract as `ring_attention`: q/k/v are
    (B, H, T, D) with T sharded over ``axis_name``; returns output with
    the same sharding.  Requires ``H % mesh.shape[axis_name] == 0``."""
    from ..ndarray.ndarray import NDArray
    from ..ops.invoke import invoke

    sp = mesh.shape[axis_name]
    h = q.shape[1]
    if h % sp != 0:
        raise ValueError(
            f"ulysses needs num_heads ({h}) divisible by the '{axis_name}' "
            f"axis ({sp}); use ring_attention for this config")

    spec = P(batch_axis, None, axis_name, None)
    fn = jax.shard_map(
        functools.partial(ulysses_attention_local, axis_name=axis_name,
                          causal=causal, scale=scale),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
    )
    if isinstance(q, NDArray):
        return invoke(fn, (q, k, v), name="ulysses_attention")
    return fn(q, k, v)
