"""Ring attention — sequence/context parallelism over the ICI ring.

The reference has **no** sequence parallelism (SURVEY.md §5.7: long sequences
are handled only by the cuDNN RNN op and bucketing).  The TPU build makes
long-context first-class: the sequence axis is sharded over a mesh axis
(``sp``), each device holds a Q/K/V block, and K/V blocks rotate around the
ring via ``ppermute`` while a blockwise (online-softmax) accumulator keeps
the attention numerically exact — compute on the current block overlaps the
ICI transfer of the next (Liu et al., "Ring Attention with Blockwise
Transformers", 2023; see PAPERS.md).

Key-padding masks (B, T) ride the ring too: the mask shards over the same
sequence axis as K/V, the resident block's slice applies to each ring
step's scores, and the log-sum-exp merge is mask-agnostic (a masked key
simply contributes zero mass to its step's partial) — so padded
variable-length batches stay on the sp + flash fast path.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = ["ring_attention", "ring_attention_local"]


def ring_attention_local(q, k, v, axis_name, causal=False, scale=None,
                         extra_vary_axes=(), use_flash=False, mask=None):
    """Per-shard body (runs under shard_map).

    q/k/v: (B, H, T_local, D) — the local sequence block.  Returns the exact
    attention output for the local queries against the *global* key/value
    sequence.  ``mask``, when given, is the (B, T_local) key-padding slice
    for the LOCAL K/V block; it rotates around the ring with them.

    With ``use_flash`` the per-ring-step block attention runs through the
    Pallas flash kernel (`ops/pallas_kernels.flash_attention_with_lse`)
    instead of a dense einsum: each step produces an exact (out, lse)
    partial for the resident K/V block, merged across ring steps with
    log-sum-exp arithmetic — per-chip memory stays O(T_local * block)
    even while T_local is long.  Whether the kernel beats the einsum
    per ring step is `models/transformer.FLASH_AUTO_MIN_T*`'s question
    (set on another toolchain; nothing across chips has been timed on
    this machine: ROADMAP M8, W6).
    """
    axis_size = lax.psum(1, axis_name)
    my_idx = lax.axis_index(axis_name)
    b, h, t_q, d = q.shape
    t_k = k.shape[2]
    if scale is None:
        scale = d ** -0.5

    if use_flash:
        # NOTE for direct callers (outside the `ring_attention` entry
        # point): the pallas interpret-mode internals are invisible to
        # shard_map's variance checker — wrap with check_vma=False, as
        # ring_attention does
        return _ring_flash(q, k, v, axis_name, axis_size, my_idx, causal,
                           scale, mask)

    q32 = q.astype(jnp.float32)
    perm = [(i, (i + 1) % axis_size) for i in range(axis_size)]

    def step(carry, i):
        m, l, acc, k_cur, v_cur, mask_cur = carry
        # block that currently lives here started at ring position my_idx - i
        src_idx = (my_idx - i) % axis_size
        s = jnp.einsum("bhqd,bhkd->bhqk", q32, k_cur.astype(jnp.float32),
                       preferred_element_type=jnp.float32) * scale
        if causal:
            q_pos = my_idx * t_q + jnp.arange(t_q)
            k_pos = src_idx * t_k + jnp.arange(t_k)
            cmask = k_pos[None, :] > q_pos[:, None]
            s = jnp.where(cmask[None, None], -jnp.inf, s)
        if mask_cur is not None:
            s = jnp.where(mask_cur[:, None, None, :] != 0, s, -jnp.inf)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        # guard fully-masked rows (all -inf) against NaN
        m_safe = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
        p = jnp.exp(s - m_safe[..., None])
        p = jnp.where(jnp.isneginf(s), 0.0, p)
        correction = jnp.exp(jnp.where(jnp.isneginf(m), m_safe, m) - m_safe)
        correction = jnp.where(jnp.isneginf(m), 0.0, correction)
        l_new = l * correction + jnp.sum(p, axis=-1)
        acc_new = acc * correction[..., None] + jnp.einsum(
            "bhqk,bhkd->bhqd", p, v_cur.astype(jnp.float32),
            preferred_element_type=jnp.float32)
        k_next = lax.ppermute(k_cur, axis_name, perm)
        v_next = lax.ppermute(v_cur, axis_name, perm)
        mask_next = None if mask_cur is None else lax.ppermute(
            mask_cur, axis_name, perm)
        return (m_new, l_new, acc_new, k_next, v_next, mask_next), None

    m0 = jnp.full((b, h, t_q), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((b, h, t_q), jnp.float32)
    acc0 = jnp.zeros((b, h, t_q, d), jnp.float32)
    # fresh accumulators are device-invariant; mark them varying over the
    # ring axis (and the batch axis, when sharded) so the scan carry types
    # match the rotating k/v blocks
    vary = (axis_name,) + tuple(extra_vary_axes)
    m0, l0, acc0 = (lax.pcast(x, vary, to="varying")
                    for x in (m0, l0, acc0))
    (m, l, acc, _k, _v, _m), _ = lax.scan(
        step, (m0, l0, acc0, k, v, mask), jnp.arange(axis_size))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.astype(q.dtype)


def _ring_flash(q, k, v, axis_name, axis_size, my_idx, causal, scale,
                mask=None):
    """Flash-kernel ring body: merge per-block (out, lse) partials.

    Ring step i processes the K/V block that started at position
    my_idx - i, so step 0 is ALWAYS the local (diagonal) block — it runs
    peeled, with the causal kernel (which skips its own fully-masked
    sub-blocks), and the scanned steps all use the unmasked kernel
    (off-diagonal blocks are either fully visible or, for causal, fully
    masked — handled by discarding their lse).  No per-device branching
    between two pallas programs is needed.

    A key-padding mask needs no merge-side handling at all: each step
    passes the resident block's (B, T_local) mask slice into the kernel,
    whose lse then reports only the valid mass — masked keys weigh zero
    in the logaddexp merge, and a fully-masked block's lse sits below
    the kernel's masked-row sentinel (~-1e30) where its exp() weight
    underflows to exactly 0.

    Why causal future ring steps are NOT skipped: which steps are masked
    depends on ``my_idx`` — a per-device runtime value under SPMD — so
    skipping would need `lax.cond` around the pallas call, which this
    toolchain cannot lower under shard_map+scan; and it would not help
    wall-clock anyway: the ring is synchronous (every step ends in a
    collective ppermute), so step i's latency is set by the axis_size−i
    devices that DO compute, not by the i devices idling.  Balancing the
    causal triangle needs a different K/V layout (zigzag/striped ring),
    which changes the sharding contract — documented as the upgrade
    path, not done here."""
    from ..ops.pallas_kernels import flash_attention_with_lse

    perm = [(i, (i + 1) % axis_size) for i in range(axis_size)]
    b, h, t_q, d = q.shape

    def _block(qq, kk, vv, mm, causal_):
        return flash_attention_with_lse(qq, kk, vv, causal=causal_,
                                        scale=scale, mask=mm)

    def merge(out_acc, lse_acc, out_i, lse_i):
        lse_new = jnp.logaddexp(lse_acc, lse_i)
        # -inf lanes: exp(-inf - -inf) is NaN, and a NaN inside where()
        # still poisons gradients — sanitize the exponents themselves
        safe_new = jnp.where(jnp.isneginf(lse_new), 0.0, lse_new)
        w_old = jnp.where(jnp.isneginf(lse_acc), 0.0,
                          jnp.exp(jnp.where(jnp.isneginf(lse_acc), 0.0,
                                            lse_acc) - safe_new))
        w_i = jnp.where(jnp.isneginf(lse_i), 0.0,
                        jnp.exp(jnp.where(jnp.isneginf(lse_i), 0.0,
                                          lse_i) - safe_new))
        out_new = (out_acc * w_old[..., None] +
                   out_i.astype(jnp.float32) * w_i[..., None])
        return out_new, lse_new

    # peeled diagonal step (i = 0): the only block that needs the
    # in-kernel causal mask (same global offsets -> local pattern)
    out_d, lse_d = _block(q, k, v, mask, causal)
    out_acc = out_d.astype(jnp.float32)
    lse_acc = lse_d
    k = lax.ppermute(k, axis_name, perm)
    v = lax.ppermute(v, axis_name, perm)
    if mask is not None:
        mask = lax.ppermute(mask, axis_name, perm)

    def step(carry, i):
        out_acc, lse_acc, k_cur, v_cur, mask_cur = carry
        src_idx = (my_idx - i) % axis_size
        out_i, lse_i = _block(q, k_cur, v_cur, mask_cur, False)
        if causal:
            # blocks from the future are fully masked for every query
            lse_i = jnp.where(src_idx > my_idx, -jnp.inf, lse_i)
        out_new, lse_new = merge(out_acc, lse_acc, out_i, lse_i)
        k_next = lax.ppermute(k_cur, axis_name, perm)
        v_next = lax.ppermute(v_cur, axis_name, perm)
        mask_next = None if mask_cur is None else lax.ppermute(
            mask_cur, axis_name, perm)
        return (out_new, lse_new, k_next, v_next, mask_next), None

    if axis_size > 1:
        (out_acc, _lse, _k, _v, _m), _ = lax.scan(
            step, (out_acc, lse_acc, k, v, mask), jnp.arange(1, axis_size))
    return out_acc.astype(q.dtype)


def ring_attention(q, k, v, mesh, axis_name="sp", causal=False, scale=None,
                   batch_axis=None, use_flash=False, mask=None):
    """Sharded entry point: q/k/v are global (B, H, T, D) arrays whose T axis
    is (to be) sharded over ``axis_name``; returns attention output with the
    same sharding.  ``mask`` is an optional global (B, T) key-padding mask,
    sharded over the same sequence axis (it rotates around the ring with
    K/V).  Accepts NDArrays or jax arrays."""
    from ..ndarray.ndarray import NDArray
    from ..ops.invoke import invoke

    spec = P(batch_axis, None, axis_name, None)
    mask_spec = P(batch_axis, axis_name)
    extra = (batch_axis,) if batch_axis is not None else ()
    body = functools.partial(ring_attention_local, axis_name=axis_name,
                             causal=causal, scale=scale,
                             extra_vary_axes=extra, use_flash=use_flash)
    if mask is not None:
        def local(qd, kd, vd, md):
            return body(qd, kd, vd, mask=md)
        in_specs = (spec, spec, spec, mask_spec)
        args = (q, k, v, mask)
    else:
        local = body
        in_specs = (spec, spec, spec)
        args = (q, k, v)
    fn = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=in_specs,
        out_specs=spec,
        # pallas interpret mode's internal block dynamic_slices mix
        # varying operands with invariant grid indices, which the vma
        # checker rejects (jax suggests exactly this workaround); the
        # einsum path keeps full variance checking.  The checker being
        # off for the whole flash body is guarded by
        # test_ring_attention_flash_gradients_match_einsum_path, which
        # asserts the two bodies agree (fwd + grads) — a variance bug in
        # the flash ring/merge logic shows up there as a value mismatch
        check_vma=not use_flash,
    )
    if isinstance(q, NDArray):
        return invoke(fn, args, name="ring_attention")
    return fn(*args)
