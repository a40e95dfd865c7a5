"""A grouped matmul in Pallas: sorted rows times the weights of the group
each row belongs to, with `jax.lax.ragged_dot`'s meaning.

    grouped_matmul(rows (M, K), weights (G, K, N), load (G,) int32) -> (M, N)

Rows ``sum(load[:g]) .. sum(load[:g + 1]) - 1`` are group ``g``'s and are
multiplied by ``weights[g]``; rows past the last group belong to no group.
The contract on them, both ways: in a RESULT they stay UNWRITTEN (what lies
there may be NaN), and in an OPERAND they may hold anything, NaN included,
forward and backward, and reach no row of a group and no weight (a tile
past the last group is never read; on a tile that holds a group's boundary
the rows x W forms compute the foreign rows and store under a row mask,
and the weights' cotangent masks BOTH operands; `lax.ragged_dot` selects
likewise: my chip run, PR 37).  The caller makes a dead row's zero where
it reads the rows back, `parallel/moe.py::_gather_sum`'s select.  Three
kernels, one family (`ragged_gmm`, `ragged_gmm_t`, `ragged_tgmm` in a
device trace):

- rows x W: a grid over (column tiles of N, the row tiles PRESENT), K
  whole where it fits.  The visits are computed from `load` (which row
  tile, for which group) and scalar-prefetched; the grid's second bound is
  their number, so time follows the live rows, not the buffer.  A row tile
  that two groups share is visited once for each and stored under a row
  mask; a tile inside one group is stored whole.  The column tile is the
  outer loop, so a group's (K, tn) weight block stays in VMEM across that
  group's consecutive row tiles and the weights are read once per call.
- rows x W^T (the rows' cotangent): the same kernel, the weight block read
  as (tn, K) and contracted on its last axis: no transposed copy of the
  weights is written.
- rows^T x rows per group (the weights' cotangent): a grid over (N tiles,
  K tiles, visits), an f32 (tk, tn) accumulator zeroed where a group
  starts and stored where it ends; an empty group is visited once, to
  write its zeros.

bf16 operands multiply natively and accumulate in f32 (`_prec`); results
come back in the operands' dtype, as `ragged_dot`'s and its
differentiation rule's do.  The design is `megablox.gmm`'s
(`jax/experimental/pallas/ops/tpu/megablox/gmm.py`); what differs: K whole
(no accumulator round trip in the forward forms), masks only on the tiles
that hold a group boundary, one operand order for the transposed forms,
tiles that are constants here.

Mosaic wants K and N in multiples of 128 lanes and the rows in multiples
of bf16's 16 sublanes; any other shape takes `lax.ragged_dot`, which is
also the tests' reference.  Off TPU the kernels run interpreted.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as onp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import context as _context
from .pallas_kernels import _pick_block, _prec

__all__ = ["grouped_matmul"]

# Tile targets, fitted to each shape by `_pick_block` and `_fit`.  Settled at
# the decoder cell's shapes (35,000 live rows of a 65,536-row buffer in 16
# uneven groups, bf16; my chip run, PR 33, TPU v5 lite), ms for
# [rows, 2304] x [16, 2304, 1792] as (rows, K, N) of a tile: (256, K, N)
# 1.77, (256, K, 896) 1.81, (128, K, 896) 1.86, (512, K, N) 1.90, (512, K,
# 896) 1.93, (512, 1152, 896) 2.18, (1024, K, 896) 2.28, against
# `lax.ragged_dot` 4.32 and 1.47 at the MXU's peak; the other five calls of
# a layer order the tiles the same way (PERF.md section 6).  Fewer rows
# waste less of the tile that two groups share (16 groups: 15 visits more
# on 137), more rows bought nothing; K whole needs no accumulator round
# trip; N whole reads the rows once.
_ROWS = 256        # tm: rows of a tile
_COLS = 2304       # tn: the weight block held across a group's row tiles
_DEPTH = 2304      # tk
# the blocks above, double-buffered, pass Mosaic's default 16 MiB of scoped
# VMEM (a v5e core has 128 MiB)
_VMEM_LIMIT = 100 * 1024 * 1024


def _fit(n, want):
    """Largest multiple of 128 that divides n and is <= want (n itself
    when n <= want)."""
    if n <= want:
        return n
    return max(t for t in range(128, want + 1, 128) if n % t == 0)


def _tiling(m, k, n):
    """(tm, tk, tn) for M rows contracted over K into N columns."""
    return _pick_block(m, _ROWS), _fit(k, _DEPTH), _fit(n, _COLS)


def _visits(load, m, tm, empty_too):
    """The grid's visits, from the rows each group holds: (offsets (G+1,),
    group of each visit, row tile of each visit, number of visits).  A
    group visits every row tile it has a row in; with `empty_too` an empty
    group gets one visit (to write its zeros).  At most ``m // tm + G - 1``
    visits; entries past the count are never run."""
    g = load.shape[0]
    ends = jnp.cumsum(load, dtype=jnp.int32)
    starts = ends - load
    first = starts // tm
    tiles = jnp.where(load > 0, (ends + tm - 1) // tm - first,
                      1 if empty_too else 0)
    until = jnp.cumsum(tiles, dtype=jnp.int32)
    v = jnp.arange(m // tm + g - 1, dtype=jnp.int32)
    group = jnp.minimum(
        jnp.searchsorted(until, v, side="right", method="compare_all"),
        g - 1).astype(jnp.int32)
    tile = first[group] + v - (until[group] - tiles[group])
    offsets = jnp.concatenate([jnp.zeros(1, jnp.int32), ends])
    # one visit at least: a grid bound of 0 is not worth finding out about
    return offsets, group, jnp.clip(tile, 0, m // tm - 1), \
        jnp.maximum(until[-1], 1)


def _span(offsets, groups, tiles, v, tm):
    """(first row of visit v's tile, its group's first row, its end)."""
    g = groups[v]
    return tiles[v] * tm, offsets[g], offsets[g + 1]


def _in_group(row0, start, end, shape):
    rows = row0 + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    return (rows >= start) & (rows < end)


def _gmm_kernel(offsets, groups, tiles, lhs, rhs, out, *acc, tm, tiles_k,
                transposed):
    v, ki = pl.program_id(1), pl.program_id(2)
    dims = (((1,), (1 if transposed else 0,)), ((), ()))
    part = jax.lax.dot_general(lhs[...], rhs[...], dims,
                               precision=_prec(lhs.dtype),
                               preferred_element_type=jnp.float32)

    def store(result):
        row0, start, end = _span(offsets, groups, tiles, v, tm)
        whole = (start <= row0) & (end >= row0 + tm)

        @pl.when(whole)
        def _():
            out[...] = result.astype(out.dtype)

        @pl.when(jnp.logical_not(whole))
        def _():
            out[...] = jnp.where(_in_group(row0, start, end, out.shape),
                                 result.astype(out.dtype), out[...])

    if tiles_k == 1:
        store(part)
        return
    acc, = acc

    @pl.when(ki == 0)
    def _():
        acc[...] = part

    @pl.when(ki > 0)
    def _():
        acc[...] += part

    @pl.when(ki == tiles_k - 1)
    def _():
        store(acc[...])


@functools.partial(jax.jit, static_argnames=("transposed", "interpret",
                                             "tiling"))
def _gmm(lhs, rhs, load, transposed, interpret, tiling):
    """lhs (M, K) x rhs (G, K, N), or x rhs (G, N, K)^T when `transposed`:
    (M, N) in lhs's dtype.  Jitted, so that a model's layers share one
    trace of each shape and the call carries the kernel's name alone."""
    m, k = lhs.shape
    n = rhs.shape[1] if transposed else rhs.shape[2]
    tm, tk, tn = tiling
    tiles_k = k // tk
    offsets, groups, tiles, visits = _visits(load, m, tm, empty_too=False)

    if transposed:
        rhs_spec = pl.BlockSpec((None, tn, tk),
                                lambda ni, v, ki, o, g, t: (g[v], ni, ki))
    else:
        rhs_spec = pl.BlockSpec((None, tk, tn),
                                lambda ni, v, ki, o, g, t: (g[v], ki, ni))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(n // tn, visits, tiles_k),
        in_specs=[pl.BlockSpec((tm, tk),
                               lambda ni, v, ki, o, g, t: (t[v], ki)),
                  rhs_spec],
        out_specs=pl.BlockSpec((tm, tn),
                               lambda ni, v, ki, o, g, t: (t[v], ni)),
        scratch_shapes=[] if tiles_k == 1
        else [pltpu.VMEM((tm, tn), jnp.float32)])
    return pl.pallas_call(
        functools.partial(_gmm_kernel, tm=tm, tiles_k=tiles_k,
                          transposed=transposed),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, n), lhs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="ragged_gmm_t" if transposed else "ragged_gmm",
    )(offsets, groups, tiles, lhs, rhs)


def _tgmm_kernel(offsets, groups, tiles, lhs, rhs, out, acc, *, tm):
    v, last = pl.program_id(2), pl.num_programs(2) - 1
    g = groups[v]
    row0, start, end = _span(offsets, groups, tiles, v, tm)

    @pl.when((v == 0) | (groups[jnp.maximum(v - 1, 0)] != g))
    def _():
        acc[...] = jnp.zeros_like(acc)

    def add(a, b):
        acc[...] += jax.lax.dot_general(
            a, b, (((0,), (0,)), ((), ())), precision=_prec(a.dtype),
            preferred_element_type=jnp.float32)

    whole = (start <= row0) & (end >= row0 + tm)

    @pl.when(whole)
    def _():
        add(lhs[...], rhs[...])

    # a tile that holds a group boundary: BOTH operands' foreign rows go,
    # for what lies in a row of no group may be NaN on either side
    @pl.when(jnp.logical_not(whole) & (end > start))
    def _():
        add(*(jnp.where(_in_group(row0, start, end, x.shape), x[...],
                        jnp.zeros_like(x)) for x in (lhs, rhs)))

    @pl.when((v == last) | (groups[jnp.minimum(v + 1, last)] != g))
    def _():
        out[...] = acc[...].astype(out.dtype)


@functools.partial(jax.jit, static_argnames=("out_dtype", "interpret",
                                             "tiling"))
def _tgmm(lhs, rhs, load, out_dtype, interpret, tiling):
    """lhs (M, K)^T x rhs (M, N) over each group's rows: (G, K, N)."""
    (m, k), n = lhs.shape, rhs.shape[1]
    tm, tk, tn = tiling
    offsets, groups, tiles, visits = _visits(load, m, tm, empty_too=True)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(n // tn, k // tk, visits),
        in_specs=[pl.BlockSpec((tm, tk),
                               lambda ni, ki, v, o, g, t: (t[v], ki)),
                  pl.BlockSpec((tm, tn),
                               lambda ni, ki, v, o, g, t: (t[v], ni))],
        out_specs=pl.BlockSpec((None, tk, tn),
                               lambda ni, ki, v, o, g, t: (g[v], ki, ni)),
        scratch_shapes=[pltpu.VMEM((tk, tn), jnp.float32)])
    return pl.pallas_call(
        functools.partial(_tgmm_kernel, tm=tm),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((load.shape[0], k, n), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="ragged_tgmm",
    )(offsets, groups, tiles, lhs, rhs)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _grouped(rows, weights, load, interpret):
    return _gmm(rows, weights, load, False, interpret,
                _tiling(*rows.shape, weights.shape[2]))


def _grouped_fwd(rows, weights, load, interpret):
    return _grouped(rows, weights, load, interpret), (rows, weights, load)


def _grouped_bwd(interpret, res, ct):
    rows, weights, load = res
    (m, k), n = rows.shape, weights.shape[2]
    return (_gmm(ct, weights, load, True, interpret, _tiling(m, n, k)),
            _tgmm(rows, ct, load, weights.dtype, interpret, _tiling(m, k, n)),
            onp.zeros(load.shape, jax.dtypes.float0))


_grouped.defvjp(_grouped_fwd, _grouped_bwd)


def grouped_matmul(rows, weights, load):
    """`jax.lax.ragged_dot(rows, weights, load)`: row i of group g times
    ``weights[g]``, in the operands' dtype, differentiable in rows and
    weights (the weights' cotangent in their dtype).  The Pallas kernels
    where the shapes tile (K, N multiples of 128, M of 16, one dtype),
    `lax.ragged_dot` otherwise; `mxtpu_grouped_matmul_lowerings` counts
    which, once per trace."""
    from .. import telemetry
    (m, k), n = rows.shape, weights.shape[2]
    tiled = k % 128 == 0 and n % 128 == 0 and m % 16 == 0 \
        and rows.dtype == weights.dtype
    telemetry.counter(
        "mxtpu_grouped_matmul_lowerings", "grouped matmuls traced, by the "
        "implementation their shapes took", labelnames=("path",)
    ).labels(path="pallas" if tiled else "ragged_dot").inc()
    if tiled:
        return _grouped(rows, weights, load, not _context.on_tpu())
    return jax.lax.ragged_dot(rows, weights, load,
                              precision=_prec(rows.dtype))
