"""``mx.npx`` — NumPy-extension namespace (NN primitives + utilities).

Reference: `python/mxnet/numpy_extension/` + the `_npx.*` generated ops.
These are the ops Gluon layers call; each delegates to the pure-XLA
lowerings in `ops/nn.py` through the dispatcher.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as onp

from ..base import MXNetError
from ..context import Context, cpu, gpu, tpu, current_context, num_gpus, num_tpus
from ..ndarray.ndarray import NDArray, waitall
from ..ops import nn as _nn
from ..ops import spatial as _spatial
from ..ops import stem as _stem
from ..ops import tensor_extra as _tex
from ..ops.control_flow import foreach, while_loop, cond  # noqa: F401
from ..ops.invoke import invoke, is_recording, is_training
from ..ops.aux_scope import apply_aux_update
from .. import random as _rng
from ..util import set_np, reset_np, is_np_array, use_np  # noqa: F401

__all__ = [
    "activation", "batch_norm", "convolution", "deconvolution", "dropout",
    "embedding", "fully_connected", "layer_norm", "group_norm", "instance_norm",
    "leaky_relu", "log_softmax", "masked_softmax", "masked_log_softmax",
    "one_hot", "pick", "pooling", "relu", "sigmoid", "smooth_l1", "softmax",
    "topk", "batch_dot", "sequence_mask", "sequence_last", "sequence_reverse",
    "reshape_like", "arange_like", "gamma", "gamma_fn", "gelu", "gammaln", "erf", "erfinv",
    "adaptive_avg_pool2d", "l2_normalization", "waitall", "cpu", "gpu", "tpu",
    "num_gpus", "num_tpus", "current_context", "save", "load", "seed",
    "foreach", "while_loop", "cond", "flash_attention", "remat",
    "gather_nd", "scatter_nd", "broadcast_like", "slice_like", "khatri_rao",
    "ravel_multi_index", "unravel_index", "make_loss", "multi_all_finite",
    "reset_arrays", "grid_generator", "bilinear_sampler",
    "spatial_transformer", "roi_pooling", "im2col", "col2im",
    "reshape", "nonzero", "index_add", "index_update", "constraint_check",
    "stem_conv",
]

seed = _rng.seed


def _op(fun, name, differentiable=True):
    def fn(*args, **kwargs):
        return invoke(fun, args, kwargs, name=name, differentiable=differentiable)
    fn.__name__ = name
    return fn


activation = _op(_nn.activation, "activation")
convolution = _op(_nn.convolution, "convolution")
stem_conv = _op(_stem.stem_conv_auto, "stem_conv")
deconvolution = _op(_nn.deconvolution, "deconvolution")
fully_connected = _op(_nn.fully_connected, "fully_connected")
pooling = _op(_nn.pooling, "pooling")
adaptive_avg_pool2d = _op(_nn.adaptive_avg_pool2d, "adaptive_avg_pool2d")
layer_norm = _op(_nn.layer_norm, "layer_norm")
group_norm = _op(_nn.group_norm, "group_norm")
instance_norm = _op(_nn.instance_norm, "instance_norm")
l2_normalization = _op(_nn.l2_normalization, "l2_normalization")
softmax = _op(_nn.softmax, "softmax")
log_softmax = _op(_nn.log_softmax, "log_softmax")
masked_softmax = _op(_nn.masked_softmax, "masked_softmax")
masked_log_softmax = _op(_nn.masked_log_softmax, "masked_log_softmax")
leaky_relu = _op(_nn.leaky_relu, "leaky_relu")
_dense_embedding = _op(_nn.embedding, "embedding")


def embedding(data, weight, input_dim=None, output_dim=None, dtype=None,
              sparse_grad=False):
    """Embedding lookup.  With ``sparse_grad=True`` on the eager tape, the
    recorded backward emits a row-sparse cotangent (O(batch·dim) HBM, not
    O(vocab·dim)) — see `ops/sparse_grad.py`; under a hybridize trace the
    dense path runs and XLA fuses the scatter."""
    if sparse_grad and is_recording():
        from ..ops.sparse_grad import sparse_embedding
        from ..ndarray.ndarray import NDArray as _ND
        if isinstance(weight, _ND) and not isinstance(
                weight._data, jax.core.Tracer):
            return sparse_embedding(data, weight, dtype=dtype)
    return _dense_embedding(data, weight, input_dim=input_dim,
                            output_dim=output_dim, dtype=dtype)
one_hot = _op(_nn.one_hot, "one_hot", differentiable=False)
pick = _op(_nn.pick, "pick")
topk = _op(_nn.topk, "topk", differentiable=False)
batch_dot = _op(_nn.batch_dot, "batch_dot")
sequence_mask = _op(_nn.sequence_mask, "sequence_mask")
sequence_last = _op(_nn.sequence_last, "sequence_last")
sequence_reverse = _op(_nn.sequence_reverse, "sequence_reverse")
smooth_l1 = _op(_nn.smooth_l1, "smooth_l1")
reshape_like = _op(_nn.reshape_like, "reshape_like")
arange_like = _op(_nn.arange_like, "arange_like", differentiable=False)
gamma = _op(_nn.gamma_fn, "gamma")
gamma_fn = gamma


# structural/indexing ops (reference `src/operator/tensor/indexing_op.cc`,
# `ravel.cc`, `contrib/krprod.cc`, `make_loss.cc`, `contrib/multi_all_finite.cc`)
gather_nd = _op(_tex.gather_nd, "gather_nd")
scatter_nd = _op(_tex.scatter_nd, "scatter_nd")
broadcast_like = _op(_tex.broadcast_like, "broadcast_like")
slice_like = _op(_tex.slice_like, "slice_like")
khatri_rao = _op(_tex.khatri_rao, "khatri_rao")
ravel_multi_index = _op(_tex.ravel_multi_index, "ravel_multi_index",
                        differentiable=False)
make_loss = _op(_tex.make_loss, "make_loss")
multi_all_finite = _op(_tex.multi_all_finite, "multi_all_finite",
                       differentiable=False)


unravel_index = _op(_tex.unravel_index, "unravel_index",
                    differentiable=False)


def reset_arrays(*arrays, num_arrays=None):
    """Zero each array in place (reference `contrib/reset_arrays.cc`,
    used to clear gradient buffers between iterations)."""
    for a in arrays:
        a[:] = 0


# spatial transformer family (reference `grid_generator.cc`,
# `bilinear_sampler.cc`, `spatial_transformer.cc`, `roi_pooling.cc`,
# `nn/im2col.h`)
grid_generator = _op(_spatial.grid_generator, "grid_generator")
bilinear_sampler = _op(_spatial.bilinear_sampler, "bilinear_sampler")
spatial_transformer = _op(_spatial.spatial_transformer, "spatial_transformer")
roi_pooling = _op(_spatial.roi_pooling, "roi_pooling")
im2col = _op(_spatial.im2col, "im2col")
col2im = _op(_spatial.col2im, "col2im")


def flash_attention(*args, **kwargs):
    """Blockwise (flash) attention Pallas kernel — lazy import so the core
    namespace does not pay the jax.experimental.pallas import cost (see
    `ops/pallas_kernels.py`).  Accepts ``mask`` (key-padding (B, T)),
    ``bias`` (additive scores, constant — no gradient), and in-kernel
    ``dropout``; when dropout is requested without an explicit ``key``
    one is drawn from the `mx.random` stream (so hybridize /
    FusedTrainStep traces get fresh masks every step, and
    `mx.random.seed` makes them reproducible)."""
    from ..ops.pallas_kernels import flash_attention as _fa
    if kwargs.get("dropout") and kwargs.get("key") is None:
        kwargs["key"] = _rng.new_key()
    return _fa(*args, **kwargs)


def remat(fn, like=None):
    """Rematerialization boundary (TPU-native; no reference analogue —
    the reference trades memory for recompute only via its nnvm mirror
    pass, `src/nnvm/gradient.cc:699`).  Wraps an NDArray-function (or a
    Block) so that, under a compiled trace (hybridize / FusedTrainStep),
    its intermediates are NOT saved for backward but recomputed from the
    boundary's inputs — `jax.checkpoint` semantics, the standard
    long-context memory lever.  Closed-over parameters are saved as
    residuals (not recomputed), and RNG draws replay deterministically
    (the mask a recomputed dropout applies is bit-identical).

    Usage: ``x = npx.remat(layer)(x)`` or build transformer stacks with
    ``remat=True``.

    When ``fn`` is a Block, its parameters are routed through the
    boundary as EXPLICIT differentiable inputs (an inner parameter
    override scope, the hybridize-trace mechanism): the eager autograd
    tape sees them and their gradients flow.  Auxiliary-state updates
    (BatchNorm moving stats) are captured inside the boundary and
    re-applied outside it — eagerly, or deferred to the enclosing trace
    scope, exactly as `gluon/block.py:_scoped_forward` chains them.
    A plain closure is differentiated only w.r.t. its array arguments —
    under ``autograd.record()`` gradients would silently not reach
    closed-over parameters, so that combination warns.

    The wrapper is cached on ``fn``, so repeated ``npx.remat(layer)``
    calls (TransformerEncoder does one per forward) reuse one closure —
    keeping `invoke`'s cached-executable fast path eligible on the
    eager tape instead of re-tracing the subgraph every step.

    ``like`` is a Block that ``fn`` (a Block) repeats: the same classes
    built with the same arguments, so the same parameter names, shapes and
    dtypes.  ``fn`` then runs through ``like``'s boundary on its own
    parameters, and blocks that name one ``like`` share one Python trace of
    its forward where each would be traced on its own.  Under an enclosing
    trace such a boundary is inlined at each call, so that the program is
    the one the blocks traced apart give: XLA schedules a step around one
    shared call otherwise, and spent 0.3 GB more temporaries on it in a
    step of six decoder layers.  Auxiliary updates reach ``fn``'s own
    parameters; attributes that ``like``'s blocks set on themselves while
    they are traced are not set on ``fn``'s.
    """
    cached = getattr(fn, "_npx_remat_wrapped", None)
    if cached is not None:
        return cached

    import warnings

    from ..ndarray.ndarray import NDArray
    from ..ops.control_flow import _wrap, _raw
    from ..ops.invoke import (set_recording, set_training,
                              set_backward_expected, is_backward_expected)
    from ..ops.aux_scope import aux_update_scope

    state = {"params": None}
    raw_cache = {}    # (training, backward) -> (jitted raw, aux_holder)

    def _make_raw(training, backward):
        """One jitted boundary per mode: dropout/BN train-vs-eval and
        the flash crossover are trace-time decisions, so sharing one
        cache across modes would freeze the first-seen mode into every
        call (the same reason HybridBlock keys _jit_cache on mode).
        Each call also takes a FRESH PRNG key so dropout masks differ
        per step instead of baking the trace-time key as a constant."""
        from ..gluon.parameter import _param_override_scope

        aux_holder = []   # Parameter targets, captured at trace time;
                          # per mode: an eval trace captures NO updates
                          # and must not clobber the train list

        def raw(key, pd_, a_, kw_):
            @jax.checkpoint
            def inner(key2, pd2, a2, kw2):
                mapping = {}
                for p, d in zip(state["params"], pd2):
                    nd = NDArray(d)
                    nd._param_ref = p
                    mapping[id(p)] = nd
                aw, kww = _wrap((a2, kw2))
                prev_tr = set_training(training)
                prev_bwd = set_backward_expected(backward)
                try:
                    with _param_override_scope(mapping), \
                            _rng.key_stream_scope(key2), \
                            aux_update_scope() as aux:
                        out = fn(*aw, **kww)
                finally:
                    set_training(prev_tr)
                    set_backward_expected(prev_bwd)
                aux_holder.clear()
                aux_holder.extend(getattr(a, "_param_ref", None)
                                  for a, _v in aux.updates)
                aux_datas = [v._data if isinstance(v, NDArray) else v
                             for _a, v in aux.updates]
                return _raw(out), aux_datas
            return inner(key, pd_, a_, kw_)
        # jitted: on the eager tape, invoke's lazy cached-executable path
        # (ops/invoke.py) needs a jax.stages.Wrapped with stable identity
        # — otherwise every training step re-traces the whole subgraph
        return jax.jit(raw, inline=like is not None), aux_holder

    def params_of(args, kwargs):
        """``fn``'s parameters in the boundary's order, resolved once."""
        params = state["params"]
        if params is None:
            if hasattr(fn, "collect_params"):
                pd = fn.collect_params()
                # deferred shapes must materialize OUTSIDE the boundary's
                # trace (fresh param buffers inside it would leak as
                # tracers); training is forced off so the probe forward
                # does not double-apply BN moving stats or burn RNG draws
                if any(p._deferred_init is not None for p in pd.values()):
                    prev = set_recording(False)
                    prev_tr = set_training(False)
                    try:
                        fn(*args, **kwargs)
                    finally:
                        set_recording(prev)
                        set_training(prev_tr)
                    pd = fn.collect_params()
                params = [pd[k] for k in sorted(pd)]
                state["names"] = sorted(pd)
            else:
                params = []
                if is_recording():
                    warnings.warn(
                        "npx.remat over a non-Block callable under "
                        "autograd.record(): gradients will not flow to "
                        "parameters closed over by the callable — wrap "
                        "the Block itself", stacklevel=3)
            # collect_params + sort walked once, not per step (a 24-layer
            # remat stack would otherwise rewalk every subtree each step)
            state["params"] = params
        return params

    def boundary(pdatas, args, kwargs):
        """(out, [(one of ``fn``'s parameters, its auxiliary update)]) of
        ``fn`` on the parameter values ``pdatas``."""
        mode = (is_training(), is_backward_expected())
        hit = raw_cache.get(mode)
        if hit is None:
            hit = raw_cache[mode] = _make_raw(*mode)
        raw, aux_holder = hit
        key = _rng.new_key()
        out, aux_vals = invoke(raw, (key, pdatas, args, kwargs),
                               name="remat")
        return out, list(zip(aux_holder, aux_vals))

    shared = None
    if like is not None and like is not fn:
        if not hasattr(fn, "collect_params"):
            raise ValueError("npx.remat(like=) takes a Block")
        shared = remat(like)

    def wrapped(*args, **kwargs):
        from ..ops.aux_scope import apply_aux_update

        params = params_of(args, kwargs)
        pdatas = [p.data() for p in params]
        if shared is None:
            out, updates = boundary(pdatas, args, kwargs)
        else:
            own = state.get("own")      # like's parameter -> fn's, by id
            if own is None:
                traced = shared.params_of(args, kwargs)
                if shared.state["names"] != state["names"] or any(
                        (t.shape, t.dtype) != (p.shape, p.dtype)
                        for t, p in zip(traced, params)):
                    raise ValueError(f"{fn.name} does not repeat {like.name}")
                own = state["own"] = {id(t): p
                                      for t, p in zip(traced, params)}
            out, updates = shared.boundary(pdatas, args, kwargs)
            updates = [(own.get(id(t), t), v) for t, v in updates]
        for p, v in updates:
            if p is not None:
                tgt = p.data()
                # tag the target so an ENCLOSING trace scope (hybridize
                # around this boundary) can resolve it back to the
                # Parameter when it applies its deferred updates
                tgt._param_ref = p
                apply_aux_update(tgt, v)
        return out

    wrapped.params_of, wrapped.boundary, wrapped.state = \
        params_of, boundary, state
    try:
        fn._npx_remat_wrapped = wrapped
    except AttributeError:
        pass
    return wrapped


def gelu(data, approximation="erf"):
    """GELU activation: exact erf form or tanh approximation (the same
    lowerings `leaky_relu` act_type='gelu'/'gelu_tanh' uses)."""
    act = "gelu" if approximation in ("erf", "none", None) else "gelu_tanh"
    return leaky_relu(data, act_type=act)
gammaln = _op(_nn.gammaln, "gammaln")
erf = _op(_nn.erf, "erf")
erfinv = _op(_nn.erfinv, "erfinv")
relu = _op(_nn.relu, "relu")
sigmoid = _op(_nn.sigmoid, "sigmoid")


def dropout(data, p=0.5, axes=None, mode=None):
    """Reference: `src/operator/nn/dropout.cc`.  Active only in train mode
    (autograd train_mode flag), like the reference's `mode='training'`."""
    training = is_training() if mode is None else (mode == "always")
    if not training or p == 0.0:
        return data
    key = _rng.new_key()
    return invoke(lambda x: _nn.dropout(x, key, p=p, axes=axes), (data,),
                  name="dropout")


def batch_norm(x, gamma, beta, running_mean, running_var, eps=1e-5,
               momentum=0.9, fix_gamma=False, use_global_stats=False,
               output_mean_var=False, axis=1):
    """Reference: `src/operator/nn/batch_norm.cc`.  Mutates the moving stats
    in train mode (deferred under a hybridize trace, see `ops/aux_scope.py`)."""
    if fix_gamma:
        gamma = gamma * 0 + 1  # reference sets gamma to 1 and zeroes its grad
    training = is_training() and not use_global_stats
    if training:
        out, new_mean, new_var = invoke(
            _nn.batch_norm_train,
            (x, gamma, beta, momentum, eps, axis, running_mean, running_var),
            name="batch_norm")
        apply_aux_update(running_mean, new_mean)
        apply_aux_update(running_var, new_var)
        return out
    return invoke(
        _nn.batch_norm_inference,
        (x, gamma, beta, running_mean, running_var, eps, axis),
        name="batch_norm")


# ---------------------------------------------------------------------------
# parameter serialization (reference: mx.npx.save/load over the 0x112 NDArray
# file format, `src/ndarray/ndarray.cc:1729`).  TPU build uses .npz — see
# mxnet_tpu/utils/serialization.py for the format note.
# ---------------------------------------------------------------------------
def save(fname, data):
    from ..utils.serialization import save_ndarrays
    save_ndarrays(fname, data)


def load(fname, ctx=None):
    from ..utils.serialization import load_ndarrays
    return load_ndarrays(fname, ctx=ctx)


# ---------------------------------------------------------------------------
# npx.reshape with data-manipulation codes -1..-6
# (reference `_npx_reshape`, `src/operator/numpy/np_matrix_op.cc:202-312`
# NumpyXReshapeInferShape; doc `python/mxnet/_numpy_op_doc.py:563`)
# ---------------------------------------------------------------------------
def _npx_reshape_infer(src, target):
    """Resolve a newshape containing codes -1..-6 against static ``src``."""
    out = []
    unknown_axis = -1
    known_prod = 1
    src_inx = 0
    i = 0
    n = len(target)
    while i < n:
        d = target[i]
        if d == -1:
            if unknown_axis >= 0:
                raise ValueError("One and only one dim can be inferred")
            unknown_axis = len(out)
            out.append(-1)
            src_inx += 1
        elif d == -2:
            out.append(src[src_inx])
            known_prod *= src[src_inx]
            src_inx += 1
        elif d == -3:
            if src[src_inx] != 1:
                raise ValueError(
                    "-3 index should only be used to skip dimension size 1")
            src_inx += 1
        elif d == -4:
            while src_inx < len(src):
                known_prod *= src[src_inx]
                out.append(src[src_inx])
                src_inx += 1
        elif d == -5:
            d1, d2 = src[src_inx], src[src_inx + 1]
            src_inx += 2
            known_prod *= d1 * d2
            out.append(d1 * d2)
        elif d == -6:
            d0 = src[src_inx]
            src_inx += 1
            d1, d2 = target[i + 1], target[i + 2]
            i += 2
            if d1 == -1 and d2 == -1:
                raise ValueError("Split dims cannot both be -1.")
            if d1 == -1:
                d1 = d0 // d2
            if d2 == -1:
                d2 = d0 // d1
            if d1 * d2 != d0:
                raise ValueError(
                    f"Split dims {d1}, {d2} do not divide original dim {d0}")
            known_prod *= d0
            out.extend([d1, d2])
        elif d >= 0:
            known_prod *= d
            out.append(d)
            src_inx += 1
        else:
            raise ValueError(f"Dimension size must be >= -6, got {d}")
        i += 1
    if unknown_axis >= 0:
        total = 1
        for s in src:
            total *= s
        if known_prod == 0 or total % known_prod:
            raise ValueError(
                f"cannot reshape {tuple(src)} into {tuple(target)}")
        out[unknown_axis] = total // known_prod
    return tuple(out)


def reshape(a, newshape, reverse=False, order="C"):
    """Reshape with the reference's -1..-6 manipulation codes
    (`_npx_reshape`); ``reverse=True`` resolves codes right-to-left."""
    if isinstance(newshape, int):
        newshape = (newshape,)
    src = tuple(int(s) for s in a.shape)
    tgt = tuple(int(t) for t in newshape)
    if reverse:
        shape = _npx_reshape_infer(src[::-1], tgt[::-1])[::-1]
    else:
        shape = _npx_reshape_infer(src, tgt)
    return invoke(lambda x: jnp.reshape(x, shape), (a,), name="npx_reshape")


def nonzero(a):
    """Indices of nonzero elements as an (N, ndim) int64-style tensor
    (reference `_npx_nonzero`, `src/operator/numpy/np_nonzero_op.cc`).
    Data-dependent output shape: eager-only (documented XLA gap; the
    reference GPU op synchronizes for the count the same way)."""
    import numpy as _onp

    host = _onp.asarray(a._data if isinstance(a, NDArray) else a)
    idx = _onp.argwhere(host)
    from ..numpy import array as _array
    return _array(idx.astype(_onp.int64))


def index_add(a, ind, val):
    """Scatter-add ``val`` at positions ``ind`` (reference
    `_npx_index_add`, doc `python/mxnet/_numpy_op_doc.py:629`): ``ind`` is
    (ndim_indexed, N) — column k addresses one position; repeated
    positions accumulate."""
    def f(x, indices, v):
        cols = tuple(indices[i] for i in range(indices.shape[0]))
        vb = jnp.broadcast_to(
            v, (indices.shape[1],) + x.shape[indices.shape[0]:]) \
            if v.ndim < x.ndim - indices.shape[0] + 1 else v
        return x.at[cols].add(vb.astype(x.dtype))

    return invoke(f, (a, ind, val), name="index_add")


def index_update(a, ind, val):
    """Scatter-set variant of :func:`index_add` (reference
    `_npx_index_update`); last write wins on duplicates."""
    def f(x, indices, v):
        cols = tuple(indices[i] for i in range(indices.shape[0]))
        vb = jnp.broadcast_to(
            v, (indices.shape[1],) + x.shape[indices.shape[0]:]) \
            if v.ndim < x.ndim - indices.shape[0] + 1 else v
        return x.at[cols].set(vb.astype(x.dtype))

    return invoke(f, (a, ind, val), name="index_update")


def constraint_check(data, msg="Constraint violated!"):
    """All-true check on a boolean tensor (reference
    `_npx_constraint_check`, `src/operator/numpy/np_constraint_check.cc`):
    raises ValueError(msg) if any element is False, else returns
    scalar True so it can be multiplied into the graph."""
    import numpy as _onp

    host = _onp.asarray(data._data if isinstance(data, NDArray) else data)
    if not bool(host.all()):
        raise ValueError(msg)
    from ..numpy import array as _array
    return _array(True)
