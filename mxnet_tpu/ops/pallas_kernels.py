"""Hand-written Pallas TPU kernels for hot ops.

The reference hand-writes CUDA for its hot paths (`src/operator/fusion/`,
cuDNN bindings); here the analogous escape hatch is Pallas.  XLA's own
fusion covers most of the op surface — these kernels exist for the few
patterns where explicit blocking wins: flash attention keeps the (T, T)
score matrix out of HBM entirely, streaming K/V blocks through VMEM with
an online-softmax accumulator (single-chip analogue of
`parallel/ring_attention.py`, which does the same blockwise math across
chips).

Design notes (the reasons are structural; the timings that first argued
for them were taken under jax 0.4.37 on another machine and are in git
history at 1f4c461, not in this tree):

- **Blocks auto-size to q=512, k=1024** (largest power-of-two divisor
  of T from those targets, `_pick_block`).  Small blocks (128x128 is
  ~131k grid steps of tiny matmuls at T=8192) pay Mosaic's
  per-iteration overhead on every step; wide K blocks amortize the
  per-block VPU softmax chain (see the `_BLOCK_TARGET_*` note).
- **Dots run in the input dtype** (bf16 in production) with f32
  accumulation via `preferred_element_type` — upcasting q/k/v to f32
  *before* the dot quarters the MXU rate.  Tests feed f32 and stay
  bit-comparable to the dense oracle.
- **Every dot is the standard (m,k)x(k,n) contraction.**  Transposed
  operands are pre-transposed OUTSIDE the kernel (an XLA copy, trivial
  next to the attention FLOPs): Mosaic's lowering of the
  transposed-contraction forms onto large bf16 tiles raised
  "Bad lhs type" on this toolchain (tpu.matmul on a 512x128 bf16 tile
  with dimension_numbers [1],[1]).
- **The backward is two Pallas kernels** (dq; dk+dv) using the saved
  output and the log-sum-exp from the forward — the flash recompute
  strategy, memory O(T * block) in both directions.
- **Masks and attention dropout run in-kernel** (round 6), fwd and bwd,
  so recipe-realistic training (padded batches + attention dropout)
  never leaves this tier.  A key-padding mask streams as (B, T) blocks
  and a scalar-prefetched per-batch `kend` (1 + last valid key) drives
  the same fetch-clamp machinery the causal skip uses, so fully-masked
  padded tails move no HBM traffic and run no dots.  Dropout bits come
  from a stateless threefry2x32 hash of (key, batch*head, q_pos, k_pos)
  computed inside each kernel: the backward regenerates the exact
  forward mask from the same seed with no (B, H, T, T) materialization
  — the functional-RNG recompute contract (`numpy_extension.remat`).
  The hardware PRNG (`pltpu.prng_seed`/`prng_random_bits`) was rejected
  for this: its bits depend on draw *order*, so the k-major dkv kernel
  could not regenerate the q-major forward mask without an in-kernel
  transpose, and it has no interpret-mode lowering on this toolchain,
  which would have left the whole dropout path untestable on CPU CI.

Kernels run in interpret mode off-TPU, so they are testable on the CPU
mesh against dense oracles.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as onp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import context as _context
from .invoke import invoke

__all__ = ["flash_attention", "flash_attention_with_lse",
           "attn_dropout_mask"]

_NEG_INF = -1e30
# Rows whose running max / lse sits below this saw no valid key in any
# block: the fully-masked-row sentinel.  Real scores are O(+-1e2); the
# only way past the threshold is the _NEG_INF fill.
_MASKED_ROW = -1e29
# Flash attention's default tile: targets for the q and k block sizes,
# fitted to each T by `_pick_block` (explicit `block_q`/`block_k` win).
# Wide K rows halve the m/l merge and acc-rescale rounds of the
# per-block softmax chain; wider q blocks buy nothing (q is the outer
# grid loop); bk=2048 doubles the VMEM of the f32 score block.  Set on
# jax 0.4.37 on another machine (the sweep is in git history, 1f4c461);
# this machine has no number for it: no cell runs the kernel.  Re-pick
# it from runs of the first cell that does (ROADMAP S4: W1
# `bert_base.phase2_t512`, W6 long-sequence) and name the ledger line
# here.
_BLOCK_TARGET_Q = 512
_BLOCK_TARGET_K = 1024
# Under a sliding window the band is `window` keys wide and a q block
# computes whole every K block the band touches; every grid step, live or
# skipped, costs about 0.3 us.  Set from my chip runs, PR 32 (TPU v5 lite,
# q (2, 32, 8192, 128) on 4 key-value heads, window 1024, bf16; forward /
# forward + backward, ms): 1024 x 1024 5.46 / 21.71, 512 x 1024 6.68 /
# 24.46, 1024 x 512 8.08 / 25.93, 512 x 512 8.96 / 27.47, 256 x 1024
# 8.98 / 30.68, 256 x 512 12.69 / 38.46, 1024 x 256 16.41 / 39.16, 256 x
# 256 21.07 / 60.21 (the band's FLOPs are 1.31 ms forward at peak).  The
# same runs read full causal attention at 512 x 1024 (the default above)
# 11.78 / 42.53 and at 1024 x 1024 10.39 / 39.03: the default was not
# moved, BERT's masked and dropped-out calls have no run at that tile.
_WINDOW_BLOCK_TARGET_Q = 1024
_WINDOW_BLOCK_TARGET_K = 1024
# Odd golden-ratio constant folding the batch*head index into the
# threefry key (bijective in uint32, so distinct heads get distinct
# keys).
_BH_FOLD = 0x9E3779B9


def _prec(dt):
    """Matmul precision for kernel dots.  The package sets the ambient
    `jax_default_matmul_precision` to float32 (true-f32 reference
    semantics for f32 ops) — but a bf16 dot with fp32 contract precision
    fails Mosaic lowering here ("Bad lhs type" on the tpu.matmul), and
    the native MXU bf16-multiply/f32-accumulate path needs DEFAULT.
    f32 inputs keep HIGHEST so the f32 kernel stays true-f32."""
    return (jax.lax.Precision.DEFAULT if dt == jnp.bfloat16
            else jax.lax.Precision.HIGHEST)


def _pick_block(t, want):
    """Largest power-of-two block <= want dividing t (>=8; t itself only
    for tiny sequences or genuinely odd T).  The block targets go
    through here, so one target is legal for every concrete length
    (t=1000 -> 8).

    The floor is 8, not 128: T=1000-style lengths have no pow2 divisor
    >=128, and a whole-T fallback builds a single-block kernel whose
    (T, T) f32 score tile can blow VMEM at large T — a small block is
    slow but correct; sizes below 8 lose the f32 sublane tile and can't
    happen for even T anyway."""
    if t <= want:
        return t
    b = want
    while b >= 8:
        if t % b == 0:
            return b
        b //= 2
    return t  # odd T: no pow2 divisor at all — degenerate, single block


def _causal_mask(s, qi, ki, block_q, block_k, transposed=False,
                 window=None):
    """Mask s (q-major), or s^T when ``transposed`` (k-major rows).  With
    a ``window`` a query also loses the keys more than window-1 behind."""
    q_ax, k_ax = (1, 0) if transposed else (0, 1)
    q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, q_ax)
    k_pos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, k_ax)
    seen = q_pos >= k_pos
    if window is not None:
        seen = seen & (q_pos - k_pos < window)
    return jnp.where(seen, s, _NEG_INF)


def _k_range(qi, block_q, block_k, nk, causal, window):
    """(first, last) K block that q block ``qi`` sees any key of."""
    last = ((qi + 1) * block_q - 1) // block_k if causal else nk - 1
    first = 0 if window is None else \
        jnp.maximum(qi * block_q - (window - 1), 0) // block_k
    return first, last


def _q_range(ki, block_q, block_k, nq, causal, window):
    """(first, last) Q block that sees any key of K block ``ki``."""
    first = (ki * block_k) // block_q if causal else 0
    last = nq - 1 if window is None else \
        jnp.minimum(((ki + 1) * block_k + window - 2) // block_q, nq - 1)
    return first, last


def _between(i, first, last, causal, window, diagonal="last"):
    """The block-skip predicate of a causal (and windowed) grid step: the
    causal bound alone (``last`` on a q-major grid, ``first`` on a k-major
    one), both under a window, None when every block is live."""
    if not causal:
        return None
    if window is not None:
        return (i >= first) & (i <= last)
    return i <= last if diagonal == "last" else i >= first


# ---------------------------------------------------------------------------
# stateless in-kernel PRNG for attention dropout
# ---------------------------------------------------------------------------
def _rotl32(x, r):
    return (x << jnp.uint32(r)) | (x >> jnp.uint32(32 - r))


def _threefry2x32(k0, k1, c0, c1):
    """Threefry-2x32 (20 rounds, Random123/JAX spec), first output word.

    Pure elementwise uint32 arithmetic, so it lowers identically under
    Mosaic and interpret mode and is position-stateless: the same
    (key, counter) pair yields the same bits in ANY kernel, any block
    shape, any traversal order — what lets the q-major forward and the
    k-major dkv backward regenerate one dropout mask.  Verified
    bit-identical to `jax._src.prng.threefry_2x32` in tests."""
    ks2 = jnp.uint32(0x1BD11BDA) ^ k0 ^ k1
    x0 = c0 + k0
    x1 = c1 + k1
    rot = ((13, 15, 26, 6), (17, 29, 16, 24))
    inj = ((k1, ks2), (ks2, k0), (k0, k1), (k1, ks2), (ks2, k0))
    for i, (a, b) in enumerate(inj):
        for r in rot[i % 2]:
            x0 = x0 + x1
            x1 = _rotl32(x1, r)
            x1 = x1 ^ x0
        x0 = x0 + a
        x1 = x1 + b + jnp.uint32(i + 1)
    return x0


def _keep_threshold(keep):
    """uint32 threshold with P(bits < threshold) = keep."""
    return min(int(round(keep * 4294967296.0)), 4294967295)


def _seed_words(key):
    """(2,) uint32 seed from a jax PRNG key (or raw uint32 words)."""
    if hasattr(key, "dtype") and jnp.issubdtype(key.dtype, jnp.integer):
        kd = jnp.ravel(key)
    else:
        kd = jax.random.key_data(key).ravel()
    return jnp.concatenate([kd, kd])[:2].astype(jnp.uint32)


def _keep_scale(seed_ref, bh, qi, ki, block_q, block_k, shape, thr,
                inv_keep, transposed=False):
    """Dropout keep/rescale factor block: inv_keep where the element's
    threefry draw keeps it, else 0.  Seeded per (key, batch*head) with
    global (q_pos, k_pos) counters, so every kernel regenerates the
    identical mask regardless of block orientation."""
    q_ax, k_ax = (1, 0) if transposed else (0, 1)
    q_pos = (qi * block_q +
             jax.lax.broadcasted_iota(jnp.int32, shape, q_ax)).astype(
        jnp.uint32)
    k_pos = (ki * block_k +
             jax.lax.broadcasted_iota(jnp.int32, shape, k_ax)).astype(
        jnp.uint32)
    k0 = seed_ref[0] ^ (bh.astype(jnp.uint32) * jnp.uint32(_BH_FOLD))
    bits = _threefry2x32(k0, seed_ref[1], q_pos, k_pos)
    return jnp.where(bits < jnp.uint32(thr), inv_keep, 0.0).astype(
        jnp.float32)


def attn_dropout_mask(key, b, h, t_q, t_k, dropout):
    """The exact keep/rescale mask the kernels regenerate fwd AND bwd:
    (B, H, T_q, T_k) f32 of {0, 1/keep}.  Dense-oracle helper — tests
    multiply it into a reference softmax to prove kernel parity; never
    materialized on the production path."""
    keep = 1.0 - float(dropout)
    seed = _seed_words(key)
    thr = jnp.uint32(_keep_threshold(keep))
    bh = jnp.arange(b * h, dtype=jnp.uint32).reshape(b * h, 1, 1)
    qp = jnp.arange(t_q, dtype=jnp.uint32).reshape(1, t_q, 1)
    kp = jnp.arange(t_k, dtype=jnp.uint32).reshape(1, 1, t_k)
    k0 = seed[0] ^ (bh * jnp.uint32(_BH_FOLD))
    bits = _threefry2x32(jnp.broadcast_to(k0, (b * h, t_q, t_k)),
                         seed[1], qp, kp)
    mask = jnp.where(bits < thr, 1.0 / keep, 0.0).astype(jnp.float32)
    return mask.reshape(b, h, t_q, t_k)


# ---------------------------------------------------------------------------
# mask plumbing
# ---------------------------------------------------------------------------
def _norm_mask(mask):
    """Key-padding mask (B, T_k), any dtype -> int32 0/1."""
    if mask.ndim != 2:
        raise ValueError(
            f"flash_attention mask must be a (batch, key_len) key-padding "
            f"mask; got ndim={mask.ndim} (full (b, t, s) attention masks "
            "take the dense path)")
    return (mask != 0).astype(jnp.int32)


def _kend(mi):
    """(B,) int32: 1 + index of the last valid key (0 when none).  The
    scalar-prefetched skip bound: K blocks at or past it are fully
    masked, so the grid skips their compute and clamps their fetch —
    padded tails cost neither dots nor HBM traffic."""
    t = mi.shape[1]
    first_from_end = jnp.argmax(mi[:, ::-1], axis=1)
    has = jnp.any(mi != 0, axis=1)
    return jnp.where(has, t - first_from_end, 0).astype(jnp.int32)


def _bias_4d(bias, b, h, t):
    """Normalize an additive attention bias to (B|1, H|1, T, T)."""
    if bias.ndim == 2:
        bias = bias.reshape(1, 1, *bias.shape)
    elif bias.ndim == 3:
        bias = bias.reshape(1, *bias.shape)
    bb, hb, tq, tk = bias.shape
    if tq != t or tk != t or bb not in (1, b) or hb not in (1, h):
        raise ValueError(
            f"bias shape {bias.shape} must broadcast to ({b}, {h}, {t}, {t})")
    return bias


def _bias_bh(bb, hb, h):
    """Grid-index map for a (bb*hb, T, T) bias along the b*h grid dim."""
    if bb == 1 and hb == 1:
        return lambda bh: 0
    if bb == 1:
        return lambda bh: bh % h
    if hb == 1:
        return lambda bh: bh // h
    return lambda bh: bh


def _ck_factory(block_q, block_k, causal, masked, nh, window=None):
    """Fetch-index clamp for q-major grids.  Causal: K blocks past the
    diagonal re-fetch the last valid block (copy elided by Mosaic).
    Window: blocks wholly behind the band fetch the band's first block.
    Masked: blocks past the batch row's `kend` (scalar-prefetched)
    clamp the same way, so padded tails move no HBM traffic."""
    def ck(bh, qi, ki, refs):
        j = ki
        if causal:
            j = jnp.minimum(j, ((qi + 1) * block_q - 1) // block_k)
        if window is not None:
            j = jnp.maximum(j, _k_range(qi, block_q, block_k, None, True,
                                        window)[0])
        if masked:
            kend = refs[0][bh // nh]
            j = jnp.minimum(j, jnp.maximum(kend - 1, 0) // block_k)
        return j
    return ck


def _cq_factory(block_q, block_k, causal, masked, nh, nq, window=None):
    """Fetch-index clamp for k-major grids.  Causal: Q blocks before the
    diagonal re-fetch the first valid block.  Window: Q blocks wholly
    past the band re-fetch the band's last block.  Masked: K rows
    entirely past `kend` freeze the fetch at the final q block (the
    index the previous live row ended on), so dead rows move no HBM
    traffic."""
    def cq(bh, ki, qi, refs):
        j = qi
        if causal:
            j = jnp.maximum(j, (ki * block_k) // block_q)
        if window is not None:
            j = jnp.minimum(j, _q_range(ki, block_q, block_k, nq, True,
                                        window)[1])
        if masked:
            alive = ki * block_k < refs[0][bh // nh]
            j = jnp.where(alive, j, nq - 1)
        return j
    return cq


def _sds(shape, dtype, like):
    """ShapeDtypeStruct matching ``like``'s mesh-axis variance: under
    shard_map (ring attention) `check_vma` requires pallas outputs to
    declare how they vary across mesh axes."""
    vma = getattr(jax.typeof(like), "vma", None)
    if vma:
        return jax.ShapeDtypeStruct(shape, dtype, vma=vma)
    return jax.ShapeDtypeStruct(shape, dtype)


def _resolve(qd, block_q, block_k, scale, interpret, window=None):
    """Resolve block sizes for one flash launch.  Explicit blocks win;
    otherwise `_BLOCK_TARGET_Q/_K` (`_WINDOW_BLOCK_TARGET_Q/_K` under a
    window) are fitted to T by `_pick_block`.
    (Bit-parity across tilings holds for the forward output and dq —
    the q split never reorders their accumulation; dk/dv accumulate
    across q-blocks, so only an unchanged block_q keeps them
    bit-stable.)"""
    t, d = qd.shape[2:]
    if block_q is None or block_k is None:
        want_q, want_k = (_BLOCK_TARGET_Q, _BLOCK_TARGET_K) \
            if window is None else \
            (_WINDOW_BLOCK_TARGET_Q, _WINDOW_BLOCK_TARGET_K)
        bq = _pick_block(t, want_q if block_q is None else block_q)
        bk = _pick_block(t, want_k if block_k is None else block_k)
    else:
        bq, bk = min(block_q, t), min(block_k, t)
    if t % bq or t % bk:
        raise ValueError(
            f"block sizes ({bq}, {bk}) must divide sequence length {t}; "
            "pad and mask upstream")
    sc = d ** -0.5 if scale is None else scale
    interp = (not _context.on_tpu()) if interpret is None else interpret
    return bq, bk, sc, interp


def _alive(causal_cond, masked_cond, body):
    conds = [c for c in (causal_cond, masked_cond) if c is not None]
    if not conds:
        return body()
    pred = conds[0] if len(conds) == 1 else conds[0] & conds[1]
    return pl.when(pred)(body)


def _pallas(kernel, grid, in_specs, out_specs, out_shape, scratch,
            interp, masked, operands, kend, name):
    """One entry for both regimes: a plain grid, or (masked) a
    PrefetchScalarGridSpec shipping `kend` ahead of the operands so the
    BlockSpec index maps can clamp fetches on it."""
    if masked:
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=grid, in_specs=in_specs,
            out_specs=out_specs, scratch_shapes=scratch)
        return pl.pallas_call(kernel, grid_spec=grid_spec,
                              out_shape=out_shape, interpret=interp,
                              name=name)(kend, *operands)
    return pl.pallas_call(kernel, grid=grid, in_specs=in_specs,
                          out_specs=out_specs, out_shape=out_shape,
                          scratch_shapes=scratch, interpret=interp,
                          name=name)(*operands)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------
def _fwd_kernel(*refs, scale, causal, block_q, block_k, nk, nh, masked,
                has_bias, thr, inv_keep, window=None):
    i = 1 if masked else 0
    kend_ref = refs[0] if masked else None
    q_ref, kt_ref, v_ref = refs[i:i + 3]
    i += 3
    mask_ref = bias_ref = seed_ref = None
    if masked:
        mask_ref = refs[i]
        i += 1
    if has_bias:
        bias_ref = refs[i]
        i += 1
    if thr is not None:
        seed_ref = refs[i]
        i += 1
    o_ref, lse_ref, m_ref, l_ref, acc_ref = refs[i:i + 5]

    bh = pl.program_id(0)
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    # Causal: K blocks entirely above the diagonal contribute nothing —
    # the last useful block for q block qi covers position (qi+1)*bq - 1.
    # Compute is skipped past it (and the BlockSpec index maps clamp the
    # fetch, so no HBM traffic moves either); the finish epilogue fires
    # at the last VALID block, not nk-1.  Masked: the same skip applies
    # past the batch row's kend (scalar-prefetched) — scratch state
    # persists across skipped steps, so the epilogue condition is
    # unchanged.  Window: K blocks wholly behind the band are skipped the
    # same way; a row may then see nothing of the band's first block, so
    # its exponent anchors at 0 as a fully-masked row's does.
    first_ki, last_ki = _k_range(qi, block_q, block_k, nk, causal, window)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def _compute():
        q = q_ref[0]                           # (block_q, D), input dtype
        kt = kt_ref[0]                         # (D, block_k)
        v = v_ref[0]                           # (block_k, D)

        s = jax.lax.dot_general(q, kt, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32,
                                precision=_prec(q.dtype)) * scale
        if has_bias:
            s = s + bias_ref[0].astype(jnp.float32)
        if causal:
            s = _causal_mask(s, qi, ki, block_q, block_k, window=window)
        if masked:
            s = jnp.where(mask_ref[0] != 0, s, _NEG_INF)   # (1, bk) bcast

        m_prev = m_ref[...]                    # (block_q, 1)
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        if masked or window is not None:
            # fully-masked-so-far rows: exp(s - m) would be exp(0)=1 with
            # both at _NEG_INF; anchoring the exponent at 0 keeps p = 0
            m_exp = jnp.where(m_new > _MASKED_ROW, m_new, 0.0)
        else:
            m_exp = m_new
        p = jnp.exp(s - m_exp)                 # (block_q, block_k) f32
        alpha = jnp.exp(m_prev - m_new)        # rescale of old mass
        # l accumulates the UNdropped mass (softmax normalizes before
        # dropout); only the value accumulation sees the dropped p
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=1, keepdims=True)
        if thr is not None:
            p_acc = p * _keep_scale(seed_ref, bh, qi, ki, block_q, block_k,
                                    p.shape, thr, inv_keep)
        else:
            p_acc = p
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p_acc.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=_prec(v.dtype))
        m_ref[...] = m_new

    _alive(_between(ki, first_ki, last_ki, causal, window),
           ki * block_k < kend_ref[bh // nh] if masked else None,
           _compute)

    @pl.when(ki == last_ki)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)
        lse_ref[0] = m_ref[...] + jnp.log(l)     # (block_q, 1)


def _kv_head(h, hkv):
    """Index map from a (batch*head) grid index to the row of K and V
    reshaped (B*Hkv, T, D): query head i reads key-value head
    i // (H/Hkv), so grouped-query K and V are never repeated in HBM."""
    if hkv == h:
        return lambda bh: bh
    group = h // hkv
    return lambda bh: (bh // h) * hkv + (bh % h) // group


def _flash_forward(qd, kd, vd, mask, bias, seed, causal, scale, dropout,
                   block_q, block_k, interpret, window=None):
    b, h, t, d = qd.shape
    hkv, dv = kd.shape[1], vd.shape[3]     # v's head size may differ from q.k's
    bq, bk, sc, interp = _resolve(qd, block_q, block_k, scale, interpret,
                                  window)
    nk = t // bk
    masked = mask is not None
    has_bias = bias is not None
    drop = float(dropout or 0.0)

    qr = qd.reshape(b * h, t, d)
    ktr = kd.reshape(b * hkv, t, d).swapaxes(1, 2)   # (b*hkv, D, T)
    vr = vd.reshape(b * hkv, t, dv)
    kernel = functools.partial(
        _fwd_kernel, scale=sc, causal=causal, block_q=bq, block_k=bk,
        nk=nk, nh=h, masked=masked, has_bias=has_bias,
        thr=_keep_threshold(1.0 - drop) if drop else None,
        inv_keep=1.0 / (1.0 - drop) if drop else 1.0, window=window)
    # Causal/masked: clamp the K/V fetch index for skipped (fully-masked)
    # blocks to the last valid one — an unchanged block index means Mosaic
    # elides the copy, so skipped grid steps move no HBM traffic.
    ck = _ck_factory(bq, bk, causal, masked, h, window)
    kvh = _kv_head(h, hkv)
    in_specs = [
        pl.BlockSpec((1, bq, d), lambda bh, qi, ki, *r: (bh, qi, 0)),
        pl.BlockSpec((1, d, bk),
                     lambda bh, qi, ki, *r: (kvh(bh), 0, ck(bh, qi, ki, r))),
        pl.BlockSpec((1, bk, dv),
                     lambda bh, qi, ki, *r: (kvh(bh), ck(bh, qi, ki, r), 0)),
    ]
    operands = [qr, ktr, vr]
    kend = None
    if masked:
        kend = _kend(mask)
        operands.append(mask.reshape(b, 1, t))
        in_specs.append(pl.BlockSpec(
            (1, 1, bk),
            lambda bh, qi, ki, *r: (bh // h, 0, ck(bh, qi, ki, r))))
    if has_bias:
        bb, hb = bias.shape[0], bias.shape[1]
        bmap = _bias_bh(bb, hb, h)
        operands.append(bias.reshape(bb * hb, t, t))
        in_specs.append(pl.BlockSpec(
            (1, bq, bk),
            lambda bh, qi, ki, *r: (bmap(bh), qi, ck(bh, qi, ki, r))))
    if drop:
        operands.append(seed)
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
    out, lse = _pallas(
        kernel, (b * h, t // bq, nk), in_specs,
        out_specs=[
            pl.BlockSpec((1, bq, dv), lambda bh, qi, ki, *r: (bh, qi, 0)),
            # (bh, t, 1) layout: Mosaic requires the last two block dims
            # be (multiple-of-8, multiple-of-128) or span the array, so a
            # 2-D (1, bq) lse block is unlowereable; a trailing unit lane
            # dim satisfies it (padded to one lane tile in VMEM)
            pl.BlockSpec((1, bq, 1), lambda bh, qi, ki, *r: (bh, qi, 0)),
        ],
        out_shape=[
            _sds((b * h, t, dv), qd.dtype, qr),
            _sds((b * h, t, 1), jnp.float32, qr),
        ],
        scratch=[
            pltpu.VMEM((bq, 1), jnp.float32),   # running max
            pltpu.VMEM((bq, 1), jnp.float32),   # running sum
            pltpu.VMEM((bq, dv), jnp.float32),  # output accumulator
        ],
        interp=interp, masked=masked, operands=operands, kend=kend,
        name="flash_fwd")
    return out.reshape(b, h, t, dv), lse.reshape(b, h, t)


# ---------------------------------------------------------------------------
# backward.  Standard flash backward:
#   p  = exp(s*scale - lse);  dv = p~^T do;  dp = do v^T
#   ds = p~ * dp - p * delta, all * scale   with delta = rowsum(do * o)
# where p~ is p with the dropout keep/rescale mask applied (p~ = p when
# dropout is off, collapsing to the classic ds = p * (dp - delta)).
# The dq kernel streams K/V blocks past each q block; the dkv kernel
# streams q/do blocks past each k block working in transposed (k-major)
# score space so every dot stays standard-form.  Dropout masks are
# REGENERATED from the same threefry seed (never stored); the padding
# mask re-applies to the recomputed scores, and lse values below the
# fully-masked-row sentinel anchor at 0 so dead rows produce exact-zero
# gradients instead of exp(+huge) garbage.
# ---------------------------------------------------------------------------
def _bwd_dq_kernel(*refs, scale, causal, block_q, block_k, nk, nh, masked,
                   has_bias, thr, inv_keep, window=None):
    i = 1 if masked else 0
    kend_ref = refs[0] if masked else None
    q_ref, kt_ref, k_ref, vt_ref, do_ref, lse_ref, dl_ref = refs[i:i + 7]
    i += 7
    mask_ref = bias_ref = seed_ref = None
    if masked:
        mask_ref = refs[i]
        i += 1
    if has_bias:
        bias_ref = refs[i]
        i += 1
    if thr is not None:
        seed_ref = refs[i]
        i += 1
    dq_ref, acc_ref = refs[i:i + 2]

    bh = pl.program_id(0)
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    first_ki, last_ki = _k_range(qi, block_q, block_k, nk, causal, window)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def _compute():
        q = q_ref[0]                           # (block_q, D)
        kt = kt_ref[0]                         # (D, block_k)
        k = k_ref[0]                           # (block_k, D)
        vt = vt_ref[0]                         # (D, block_k)
        do = do_ref[0]                         # (block_q, D)
        lse = lse_ref[0]                       # (block_q, 1) f32
        delta = dl_ref[0]                      # (block_q, 1) f32

        s = jax.lax.dot_general(q, kt, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32,
                                precision=_prec(q.dtype)) * scale
        if has_bias:
            s = s + bias_ref[0].astype(jnp.float32)
        if causal:
            s = _causal_mask(s, qi, ki, block_q, block_k, window=window)
        if masked:
            s = jnp.where(mask_ref[0] != 0, s, _NEG_INF)
            lse = jnp.where(lse > _MASKED_ROW, lse, 0.0)
        p = jnp.exp(s - lse)                   # (block_q, block_k) f32
        dp = jax.lax.dot_general(do, vt, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32,
                                 precision=_prec(do.dtype))
        if thr is not None:
            dp = dp * _keep_scale(seed_ref, bh, qi, ki, block_q, block_k,
                                  p.shape, thr, inv_keep)
        ds = p * (dp - delta) * scale
        acc_ref[...] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=_prec(k.dtype))

    _alive(_between(ki, first_ki, last_ki, causal, window),
           ki * block_k < kend_ref[bh // nh] if masked else None,
           _compute)

    @pl.when(ki == last_ki)
    def _finish():
        dq_ref[0] = acc_ref[...].astype(dq_ref.dtype)


def _bwd_dkv_kernel(*refs, scale, causal, block_q, block_k, nq, nh, masked,
                    has_bias, thr, inv_keep, window=None, nhkv=None):
    i = 1 if masked else 0
    kend_ref = refs[0] if masked else None
    (qt_ref, q_ref, k_ref, v_ref, dot_ref, do_ref, lse_ref,
     dl_ref) = refs[i:i + 8]
    i += 8
    mask_ref = bias_ref = seed_ref = None
    if masked:
        mask_ref = refs[i]
        i += 1
    if has_bias:
        bias_ref = refs[i]
        i += 1
    if thr is not None:
        seed_ref = refs[i]
        i += 1
    dk_ref, dv_ref, dk_acc, dv_acc = refs[i:i + 4]

    bh = pl.program_id(0)
    ki = pl.program_id(1)
    qi = pl.program_id(2)
    # Grouped-query heads: grid axis 0 runs over (batch, key-value head)
    # and axis 2 over (query head of the group, q block), so dk and dv
    # accumulate over every query head that reads this key-value head.
    group = 1 if nhkv is None else nh // nhkv
    if group > 1:
        g, qi = qi // nq, qi % nq
        bh = _q_head(nh, nhkv)(bh, g)
    # Causal, k-major: Q blocks strictly before the diagonal see nothing
    # of this K block; the first contributing block holds position ki*bk.
    # Window: nor do Q blocks wholly past ki's last key + window - 1.
    first_qi, last_qi = _q_range(ki, block_q, block_k, nq, causal, window)

    first_step = qi == first_qi
    if group > 1:
        first_step = first_step & (g == 0)

    @pl.when(first_step)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def _compute():
        qt = qt_ref[0]                         # (D, block_q)
        q = q_ref[0]                           # (block_q, D)
        k = k_ref[0]                           # (block_k, D)
        v = v_ref[0]                           # (block_k, D)
        dot_ = dot_ref[0]                      # (D, block_q)  = do^T
        do = do_ref[0]                         # (block_q, D)
        lse = lse_ref[0]                       # (1, block_q) f32
        delta = dl_ref[0]                      # (1, block_q) f32

        # k-major (transposed) score space: st[kb, qb] = s[qb, kb]
        st = jax.lax.dot_general(k, qt, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32,
                                 precision=_prec(k.dtype)) * scale
        if has_bias:
            st = st + bias_ref[0].astype(jnp.float32)
        if causal:
            st = _causal_mask(st, qi, ki, block_q, block_k, transposed=True,
                              window=window)
        if masked:
            st = jnp.where(mask_ref[0] != 0, st, _NEG_INF)  # (bk, 1) bcast
            lse = jnp.where(lse > _MASKED_ROW, lse, 0.0)
        pt = jnp.exp(st - lse)                 # (block_k, block_q)
        if thr is not None:
            ks = _keep_scale(seed_ref, bh, qi, ki, block_q, block_k,
                             pt.shape, thr, inv_keep, transposed=True)
            ptd = pt * ks                      # dropped+rescaled p~^T
        else:
            ks = None
            ptd = pt
        dv_acc[...] += jax.lax.dot_general(
            ptd.astype(do.dtype), do, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=_prec(do.dtype))
        dpt = jax.lax.dot_general(v, dot_, (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32,
                                  precision=_prec(v.dtype))
        if ks is not None:
            dpt = dpt * ks
        dst = pt * (dpt - delta) * scale
        dk_acc[...] += jax.lax.dot_general(
            dst.astype(q.dtype), q, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=_prec(q.dtype))

    _alive(_between(qi, first_qi, last_qi, causal, window, "first"),
           ki * block_k < kend_ref[bh // nh] if masked else None,
           _compute)

    last_step = qi == last_qi
    if group > 1:
        last_step = last_step & (g == group - 1)

    @pl.when(last_step)
    def _finish():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _q_head(h, hkv):
    """Index map from a (batch*key-value head) grid index and the query
    head's place in its group to the (batch*head) row of Q."""
    group = h // hkv
    return lambda bkv, g: (bkv // hkv) * h + (bkv % hkv) * group + g


def _flash_backward(qd, kd, vd, mask, bias, seed, out, lse, ct, causal,
                    scale, dropout, block_q, block_k, interpret, dlse=None,
                    window=None):
    b, h, t, d = qd.shape
    hkv, dv = kd.shape[1], vd.shape[3]
    group = h // hkv
    bq, bk, sc, interp = _resolve(qd, block_q, block_k, scale, interpret,
                                  window)
    nq, nk = t // bq, t // bk
    masked = mask is not None
    has_bias = bias is not None
    drop = float(dropout or 0.0)
    thr = _keep_threshold(1.0 - drop) if drop else None
    inv_keep = 1.0 / (1.0 - drop) if drop else 1.0

    # delta = rowsum(dO * O): cheap elementwise, XLA fuses it.  A
    # cotangent on the log-sum-exp output folds in here: d s_ij picks up
    # + p_ij * dlse_i, and ds = p * (dp - (delta - dlse)) absorbs it.
    delta = (ct.astype(jnp.float32) * out.astype(jnp.float32)).sum(-1)
    if dlse is not None:
        delta = delta - dlse.astype(jnp.float32)

    qr = qd.reshape(b * h, t, d)
    kr = kd.reshape(b * hkv, t, d)
    vr = vd.reshape(b * hkv, t, dv)
    dor = ct.reshape(b * h, t, dv)
    qtr = qr.swapaxes(1, 2)                    # (bh, D, T)
    ktr = kr.swapaxes(1, 2)
    vtr = vr.swapaxes(1, 2)
    dotr = dor.swapaxes(1, 2)
    lser = lse.reshape(b * h, t, 1)
    dltr = delta.reshape(b * h, t, 1)
    lse_row = lse.reshape(b * h, 1, t)         # k-major kernels broadcast
    dlt_row = delta.reshape(b * h, 1, t)       # over score ROWS

    ck = _ck_factory(bq, bk, causal, masked, h, window)
    cq = _cq_factory(bq, bk, causal, masked, h, nq, window)
    kvh = _kv_head(h, hkv)
    kend = _kend(mask) if masked else None
    if has_bias:
        bb, hb = bias.shape[0], bias.shape[1]
        bmap = _bias_bh(bb, hb, h)
        br = bias.reshape(bb * hb, t, t)
        btr = br.swapaxes(1, 2)                # k-major kernel reads s^T

    in_specs = [
        pl.BlockSpec((1, bq, d), lambda bh, qi, ki, *r: (bh, qi, 0)),
        pl.BlockSpec((1, d, bk),
                     lambda bh, qi, ki, *r: (kvh(bh), 0, ck(bh, qi, ki, r))),
        pl.BlockSpec((1, bk, d),
                     lambda bh, qi, ki, *r: (kvh(bh), ck(bh, qi, ki, r), 0)),
        pl.BlockSpec((1, dv, bk),
                     lambda bh, qi, ki, *r: (kvh(bh), 0, ck(bh, qi, ki, r))),
        pl.BlockSpec((1, bq, dv), lambda bh, qi, ki, *r: (bh, qi, 0)),
        pl.BlockSpec((1, bq, 1), lambda bh, qi, ki, *r: (bh, qi, 0)),
        pl.BlockSpec((1, bq, 1), lambda bh, qi, ki, *r: (bh, qi, 0)),
    ]
    operands = [qr, ktr, kr, vtr, dor, lser, dltr]
    if masked:
        operands.append(mask.reshape(b, 1, t))
        in_specs.append(pl.BlockSpec(
            (1, 1, bk),
            lambda bh, qi, ki, *r: (bh // h, 0, ck(bh, qi, ki, r))))
    if has_bias:
        operands.append(br)
        in_specs.append(pl.BlockSpec(
            (1, bq, bk),
            lambda bh, qi, ki, *r: (bmap(bh), qi, ck(bh, qi, ki, r))))
    if drop:
        operands.append(seed)
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
    dq = _pallas(
        functools.partial(_bwd_dq_kernel, scale=sc, causal=causal,
                          block_q=bq, block_k=bk, nk=nk, nh=h,
                          masked=masked, has_bias=has_bias, thr=thr,
                          inv_keep=inv_keep, window=window),
        (b * h, nq, nk), in_specs,
        out_specs=pl.BlockSpec((1, bq, d),
                               lambda bh, qi, ki, *r: (bh, qi, 0)),
        out_shape=_sds((b * h, t, d), qd.dtype, qr),
        scratch=[pltpu.VMEM((bq, d), jnp.float32)],
        interp=interp, masked=masked, operands=operands, kend=kend,
        name="flash_bwd_dq")

    # k-major grid: axis 0 over (batch, key-value head), axis 2 over q
    # blocks — of every query head of the group in turn, when grouped
    if group == 1:
        def qh(bh, j):
            return bh

        def qb(bh, ki, j, r):
            return cq(bh, ki, j, r)
    else:
        q_head = _q_head(h, hkv)

        def qh(bkv, j):
            return q_head(bkv, j // nq)

        def qb(bkv, ki, j, r):
            return cq(qh(bkv, j), ki, j % nq, r)

    in_specs = [
        pl.BlockSpec((1, d, bq),
                     lambda bh, ki, j, *r: (qh(bh, j), 0, qb(bh, ki, j, r))),
        pl.BlockSpec((1, bq, d),
                     lambda bh, ki, j, *r: (qh(bh, j), qb(bh, ki, j, r), 0)),
        pl.BlockSpec((1, bk, d), lambda bh, ki, j, *r: (bh, ki, 0)),
        pl.BlockSpec((1, bk, dv), lambda bh, ki, j, *r: (bh, ki, 0)),
        pl.BlockSpec((1, dv, bq),
                     lambda bh, ki, j, *r: (qh(bh, j), 0, qb(bh, ki, j, r))),
        pl.BlockSpec((1, bq, dv),
                     lambda bh, ki, j, *r: (qh(bh, j), qb(bh, ki, j, r), 0)),
        pl.BlockSpec((1, 1, bq),
                     lambda bh, ki, j, *r: (qh(bh, j), 0, qb(bh, ki, j, r))),
        pl.BlockSpec((1, 1, bq),
                     lambda bh, ki, j, *r: (qh(bh, j), 0, qb(bh, ki, j, r))),
    ]
    operands = [qtr, qr, kr, vr, dotr, dor, lse_row, dlt_row]
    if masked:
        # k-major: the mask selects score ROWS — column layout (B, T, 1)
        operands.append(mask.reshape(b, t, 1))
        in_specs.append(pl.BlockSpec(
            (1, bk, 1), lambda bh, ki, j, *r: (bh // hkv, ki, 0)))
    if has_bias:
        operands.append(btr)
        in_specs.append(pl.BlockSpec(
            (1, bk, bq),
            lambda bh, ki, j, *r: (bmap(qh(bh, j)), ki, qb(bh, ki, j, r))))
    if drop:
        operands.append(seed)
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
    dk, dvr = _pallas(
        functools.partial(_bwd_dkv_kernel, scale=sc, causal=causal,
                          block_q=bq, block_k=bk, nq=nq, nh=h,
                          masked=masked, has_bias=has_bias, thr=thr,
                          inv_keep=inv_keep, window=window,
                          nhkv=None if group == 1 else hkv),
        (b * hkv, nk, group * nq), in_specs,
        out_specs=[
            pl.BlockSpec((1, bk, d), lambda bh, ki, j, *r: (bh, ki, 0)),
            pl.BlockSpec((1, bk, dv), lambda bh, ki, j, *r: (bh, ki, 0)),
        ],
        out_shape=[
            _sds((b * hkv, t, d), kd.dtype, qr),
            _sds((b * hkv, t, dv), vd.dtype, qr),
        ],
        scratch=[pltpu.VMEM((bk, d), jnp.float32),
                 pltpu.VMEM((bk, dv), jnp.float32)],
        interp=interp, masked=masked, operands=operands, kend=kend,
        name="flash_bwd_dkv")

    return (dq.reshape(b, h, t, d), dk.reshape(b, hkv, t, d),
            dvr.reshape(b, hkv, t, dv))


def _zero_cts(mask, bias, seed):
    """Cotangents for the non-q/k/v inputs: float0 for the integer mask
    and seed; zeros for the (float) bias — the bias is treated as a
    CONSTANT (ALiBi-style, non-learned); see flash_attention's doc."""
    dmask = None if mask is None else onp.zeros(mask.shape,
                                                jax.dtypes.float0)
    dbias = None if bias is None else jnp.zeros_like(bias)
    dseed = None if seed is None else onp.zeros(seed.shape,
                                                jax.dtypes.float0)
    return dmask, dbias, dseed


@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(6, 7, 8, 9, 10, 11, 12))
def _flash(qd, kd, vd, mask, bias, seed, causal, scale, dropout, block_q,
           block_k, interpret, window):
    out, _lse = _flash_forward(qd, kd, vd, mask, bias, seed, causal, scale,
                               dropout, block_q, block_k, interpret, window)
    return out


def _flash_fwd(qd, kd, vd, mask, bias, seed, causal, scale, dropout,
               block_q, block_k, interpret, window):
    out, lse = _flash_forward(qd, kd, vd, mask, bias, seed, causal, scale,
                              dropout, block_q, block_k, interpret, window)
    return out, (qd, kd, vd, mask, bias, seed, out, lse)


def _flash_bwd(causal, scale, dropout, block_q, block_k, interpret, window,
               res, ct):
    qd, kd, vd, mask, bias, seed, out, lse = res
    dq, dk, dv = _flash_backward(qd, kd, vd, mask, bias, seed, out, lse,
                                 ct, causal, scale, dropout, block_q,
                                 block_k, interpret, window=window)
    return (dq, dk, dv) + _zero_cts(mask, bias, seed)


_flash.defvjp(_flash_fwd, _flash_bwd)


@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(6, 7, 8, 9, 10, 11, 12))
def _flash_lse(qd, kd, vd, mask, bias, seed, causal, scale, dropout,
               block_q, block_k, interpret, window):
    """Flash attention returning (out, lse) — the log-sum-exp output is
    what lets independently-computed attention partials merge exactly
    (ring attention's per-ring-step building block)."""
    return _flash_forward(qd, kd, vd, mask, bias, seed, causal, scale,
                          dropout, block_q, block_k, interpret, window)


def _flash_lse_fwd(qd, kd, vd, mask, bias, seed, causal, scale, dropout,
                   block_q, block_k, interpret, window):
    out, lse = _flash_forward(qd, kd, vd, mask, bias, seed, causal, scale,
                              dropout, block_q, block_k, interpret, window)
    return (out, lse), (qd, kd, vd, mask, bias, seed, out, lse)


def _flash_lse_bwd(causal, scale, dropout, block_q, block_k, interpret,
                   window, res, cts):
    qd, kd, vd, mask, bias, seed, out, lse = res
    ct, dlse = cts
    dq, dk, dv = _flash_backward(qd, kd, vd, mask, bias, seed, out, lse,
                                 ct, causal, scale, dropout, block_q,
                                 block_k, interpret, dlse=dlse,
                                 window=window)
    return (dq, dk, dv) + _zero_cts(mask, bias, seed)


_flash_lse.defvjp(_flash_lse_fwd, _flash_lse_bwd)


def _flash_over_mesh(qd, kd, vd, mi, bi, seed, *static):
    """`_flash`, as one launch per device when traced inside a
    `parallel.mesh_scope`: XLA cannot partition a Mosaic call by itself, and
    attention is independent per (batch, head).  Batch splits over the
    scope's batch axes and heads over ``tp`` (where the Megatron
    column-split Q/K/V already leaves them), each only when it divides;
    anything else is gathered.  Each shard folds its index into the
    dropout seed — the kernel counts (batch, head) from 0 on every
    device, so one seed would repeat one mask across shards."""
    from ..parallel import mesh as _mesh
    mesh, P = _mesh.current_mesh(), _mesh.PartitionSpec
    if mesh is None:
        return _flash(qd, kd, vd, mi, bi, seed, *static)
    b, h = qd.shape[:2]

    def axes_dividing(n, names):
        return tuple(names) if n % _mesh.axes_size(mesh, names) == 0 else ()

    b_ax = axes_dividing(b, _mesh.current_batch_axes())
    # fewer key-value heads than query heads: both must divide
    h_ax = axes_dividing(kd.shape[1],
                         ("tp",) if "tp" in mesh.axis_names else ())
    qkv = P(b_ax or None, h_ax or None, None, None)

    def local(qd, kd, vd, mi, bi, seed):
        if seed is not None:
            shard = jnp.uint32(0)
            for a in b_ax + h_ax:
                shard = shard * jnp.uint32(mesh.shape[a]) + \
                    jax.lax.axis_index(a).astype(jnp.uint32)
            seed = seed.at[1].add(shard * jnp.uint32(_BH_FOLD))
        return _flash(qd, kd, vd, mi, bi, seed, *static)

    bias_spec = None if bi is None else P(
        (b_ax or None) if bi.shape[0] == b else None,
        (h_ax or None) if bi.shape[1] == h else None, None, None)
    return _mesh.shard_kernel(
        local, in_specs=(qkv, qkv, qkv, P(b_ax or None, None), bias_spec,
                         P()),
        out_specs=qkv)(qd, kd, vd, mi, bi, seed)


def _entry(fn, q, k, v, causal, scale, block_q, block_k, interpret, mask,
           bias, dropout, key, name, window=None):
    from ..ndarray.ndarray import NDArray

    drop = float(dropout or 0.0)
    if not 0.0 <= drop < 1.0:
        raise ValueError(f"dropout must be in [0, 1); got {dropout}")
    if drop and key is None:
        raise ValueError(
            "flash_attention with dropout>0 needs an explicit PRNG `key` "
            "(npx.flash_attention draws one from the mx.random stream)")
    seed = _seed_words(key) if drop else None
    b, h, t = q.shape[0], q.shape[1], q.shape[2]
    if k.shape[:3] != v.shape[:3] or len(v.shape) != 4 or h % k.shape[1] \
            or k.shape[:1] + k.shape[2:] != q.shape[:1] + q.shape[2:]:
        raise ValueError(
            f"flash_attention takes k (B, Hkv, T, D) and v (B, Hkv, T, Dv) "
            f"with Hkv dividing q's {h} heads; got q {q.shape}, "
            f"k {k.shape}, v {v.shape}")
    if window is not None and (not causal or window < 1):
        raise ValueError("flash_attention's window (>= 1 keys, the query's "
                         "own among them) needs causal=True")

    def f(qd, kd, vd, maskd=None, biasd=None):
        mi = None if maskd is None else _norm_mask(maskd)
        bi = None if biasd is None else _bias_4d(biasd, b, h, t)
        return fn(qd, kd, vd, mi, bi, seed, causal, scale, drop, block_q,
                  block_k, interpret, window)

    args = (q, k, v, mask, bias)
    if any(isinstance(a, NDArray) for a in args):
        return invoke(f, args, name=name)
    return f(*args)


def flash_attention_with_lse(q, k, v, causal=False, scale=None,
                             block_q=None, block_k=None, interpret=None,
                             mask=None, bias=None, dropout=0.0, key=None,
                             window=None):
    """`flash_attention` that also returns the per-query log-sum-exp
    (B, H, T) in f32.  Partials over disjoint K/V shards merge exactly:
    ``lse = logaddexp(lse_a, lse_b); out = out_a*exp(lse_a-lse) +
    out_b*exp(lse_b-lse)`` — see `parallel/ring_attention.py`.  The lse
    is that of the UNdropped softmax (dropout rescales values only), so
    the ring merge is mask- and dropout-agnostic; rows with no valid key
    report lse below the `_MASKED_ROW` sentinel and weigh zero in any
    merge."""
    return _entry(_flash_lse, q, k, v, causal, scale, block_q, block_k,
                  interpret, mask, bias, dropout, key,
                  "flash_attention_with_lse", window)


def flash_attention(q, k, v, causal=False, scale=None, block_q=None,
                    block_k=None, interpret=None, mask=None, bias=None,
                    dropout=0.0, key=None, window=None):
    """Blockwise (flash) attention: q (B, H, T, D), k (B, Hkv, T, D),
    v (B, Hkv, T, Dv) -> (B, H, T, Dv).  v's head size may differ from
    q.k's (latent attention: 192 and 128); the default ``scale`` is
    D^-0.5.

    Grouped-query heads: Hkv divides H and query head i reads key-value
    head i // (H/Hkv) through the BlockSpec index maps — K and V are
    never repeated in HBM — and the dk+dv kernel sums over the query
    heads of a group.  ``window`` (with ``causal``): query t sees key j
    iff 0 <= t - j < window.  K blocks wholly outside that band run no
    dot and move no bytes, forward and in both backward kernels (the
    same skip and fetch-clamp as the causal diagonal's), so at T >>
    window a layer costs about window/T of a full one's blocks.

    Exact attention; the full score matrix is never materialized, in
    forward or backward (both are Pallas kernels streaming K/V blocks —
    memory stays O(T * block) against dense's O(T^2)).  Block sizes
    default to the largest power-of-two divisors of T up to 512 (q) and
    1024 (k) — measured optimum, see module notes; T must be divisible
    by the blocks (pad and mask upstream otherwise — same contract as
    the reference's fused kernels).

    ``mask``: key-padding mask (B, T), truthy = valid key.  Applied
    inside every kernel; K blocks wholly past a batch row's last valid
    key are skipped (compute AND fetch — the padded tail is free).
    Rows with NO valid key output exact 0 with zero gradients (the dense
    softmax path degenerates to uniform weights there instead; compare
    only valid rows).  ``bias``: additive score bias broadcastable to
    (B, H, T, T) — e.g. ALiBi (T, T) or per-head (H, T, T) — streamed
    blockwise, added before masking.  The bias is treated as a constant:
    no gradient flows to it (a dbias output would re-materialize the
    (B, H, T, T) score space the kernel exists to avoid).

    ``dropout``/``key``: in-kernel attention dropout — softmax weights
    are zeroed at rate ``dropout`` and survivors rescaled by 1/keep,
    with bits drawn from a stateless threefry2x32 hash of
    (key, batch*head, q_pos, k_pos).  The backward kernels regenerate
    the identical mask from the same seed: nothing is stored, and the
    fwd/bwd masks are bit-identical by construction (tested).  The
    bitstream is backend-stable (same mask on TPU and in interpret
    mode) and is NOT `npx.dropout`'s `rbg` stream — it is the
    kernel's own documented stream.

    Validated exact on real TPU (vs XLA dense).  When the (T, T) score
    matrix FITS in HBM comfortably, plain XLA attention is still faster
    — use this kernel past the crossovers
    (`models/transformer.FLASH_AUTO_MIN_T*`) and
    `parallel.ring_attention` when the sequence is sharded across chips.

    Traced inside a `parallel.mesh_scope` (a mesh-sharded
    `FusedTrainStep`) the kernel launches once per device over its share
    of (batch, heads) — see `_flash_over_mesh`; `flash_attention_with_lse`
    is the per-shard building block and never re-shards itself.
    """
    return _entry(_flash_over_mesh, q, k, v, causal, scale, block_q,
                  block_k, interpret, mask, bias, dropout, key,
                  "flash_attention", window)
