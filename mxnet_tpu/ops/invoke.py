"""Imperative op dispatch + autograd tape — the engine of the framework.

Reference analogue: `src/imperative/imperative.cc` (``Imperative::Invoke`` at
:98, ``InvokeOp`` :49, ``RecordOp``/``Backward`` :385) plus the ThreadedEngine
(`src/engine/threaded_engine.h`).  TPU-native design:

* **Scheduling**: the reference builds its own dataflow engine (read/write vars,
  per-device worker threads).  PjRt already gives async dispatch with ordered
  per-device streams and buffer-definition events, so an op here is simply a
  traced JAX call — python returns immediately, XLA executes asynchronously,
  and ``wait_to_read`` blocks on the buffer (the reference's ``WaitForVar``).
  Async errors surface at the block point, matching the reference's
  throw-at-WaitToRead contract (`src/engine/threaded_engine.h:461-498`).

* **Gradients**: the reference keeps a per-op ``FGradient`` registry and builds
  a backward nnvm graph (`src/nnvm/gradient.cc:699`).  Here the tape records a
  ``jax.vjp`` closure per invoked op — one generic rule covers the whole op
  surface, and under ``hybridize()`` an entire compiled program becomes a
  single tape node.

* **Mutation**: reference NDArrays are mutable through engine write-vars.  XLA
  buffers are immutable, so mutation is re-binding the NDArray to a new buffer
  (with a version bump).  The tape stores ``(array, node_at_use_time)`` pairs,
  so mutating an array never corrupts previously recorded history (residuals
  were captured by value) — in-place updates inside ``autograd.record()`` are
  legal, unlike torch.
"""
from __future__ import annotations

import os
import threading
from collections import defaultdict

import jax
import jax.numpy as jnp
import numpy as onp

__all__ = [
    "invoke",
    "is_recording",
    "is_training",
    "set_recording",
    "set_training",
    "backward",
    "grad",
    "Node",
]

# The NDArray class registers itself here to break the import cycle
# (analogue of `_set_ndarray_class` in `python/mxnet/ndarray/register.py`).
_ndarray_cls = None


def set_ndarray_class(cls):
    global _ndarray_cls
    _ndarray_cls = cls


class _TapeState(threading.local):
    def __init__(self):
        self.recording = False
        self.training = False
        self.backward_expected = False


_state = _TapeState()


def is_recording():
    return _state.recording


def is_training():
    return _state.training


def set_recording(flag):
    prev = _state.recording
    _state.recording = bool(flag)
    return prev


def set_training(flag):
    prev = _state.training
    _state.training = bool(flag)
    return prev


def is_backward_expected():
    """True when the current code is running (or tracing) ahead of a
    backward pass: an eager tape is recording, train-mode is on, or a
    compiled trace declared it explicitly (`_scoped_forward(backward=)`).
    Trace-time policy code (flash-attention crossover) keys on this —
    `is_recording()` alone is useless there because traces force
    recording off."""
    return (_state.backward_expected or _state.recording or
            _state.training)


def set_backward_expected(flag):
    prev = _state.backward_expected
    _state.backward_expected = bool(flag)
    return prev


class Node:
    """One recorded op on the tape.

    Reference analogue: an nnvm node created by ``Imperative::RecordOp``
    (`src/imperative/imperative.cc:134` region).  ``parents`` capture the input
    arrays *and the tape node each had at use time*, which is what makes
    mutation safe (see module docstring).
    """

    __slots__ = (
        "name",
        "vjp_fn",
        "parents",
        "out_structs",
        "out_treedef",
        "fun",
        "flat_const",
        "treedef",
        "diff_idx",
        "n_outs",
        "parent_versions",
    )

    def __init__(self, name, vjp_fn, parents, out_structs, out_treedef=None,
                 fun=None, flat_const=None, treedef=None, diff_idx=None):
        self.name = name
        self.vjp_fn = vjp_fn
        self.parents = parents  # list[(NDArray, Node|None, out_idx_in_that_node)]
        self.out_structs = out_structs  # list[jax.ShapeDtypeStruct] (flat)
        self.out_treedef = out_treedef  # pytree structure of the op's output
        self.n_outs = len(out_structs)
        # Retained only to support create_graph=True (higher-order):
        self.fun = fun
        self.flat_const = flat_const
        self.treedef = treedef
        self.diff_idx = diff_idx
        # MXNET_ENGINE_DEBUG=1 stale-read diagnostics (reference §5.2:
        # the engine's versioned vars make conflicting access visible;
        # here buffers are immutable so the tape is always CORRECT, but a
        # leaf mutated after being read means the gradient describes the
        # OLD value — worth flagging in debug mode)
        self.parent_versions = (
            [getattr(a, "_version", None) for a, _n, _i in parents]
            if _engine_debug() else None)


# Read ONCE at import (mxlint's env-read-at-trace-time contract):
# Node.__init__ consults this on every recorded op, so a per-call environ
# read was both hot-path overhead and a half-applied-config hazard — ops
# recorded before an env change carried no versions while later ones did.
# Tests toggle the module flag directly (monkeypatch.setattr).
_ENGINE_DEBUG = os.environ.get("MXNET_ENGINE_DEBUG", "0") not in ("0", "")


def _engine_debug():
    return _ENGINE_DEBUG


def _is_nd(x):
    return _ndarray_cls is not None and isinstance(x, _ndarray_cls)


def _is_float(data):
    return jnp.issubdtype(data.dtype, jnp.floating) or jnp.issubdtype(
        data.dtype, jnp.complexfloating
    )


def _attached(arr):
    """Does gradient need to flow into this array? (tape node, or grad leaf)"""
    return arr._node is not None or (arr._grad is not None and arr._grad_req != "null")


def _profiler_hook():
    """(clock, record) while the profiler runs, else None — per-op host
    dispatch spans (the engine's ProfileOperator analogue; device-side
    kernel timing comes from the XLA trace via
    `profiler.set_config(xla_trace_dir=...)`).

    The clock is the profiler's own epoch (`_now_us`), so operator events
    land on the same chrome-trace timeline as step-phase / collective /
    serve spans.  Each recorded op also bumps the telemetry registry's
    dispatch counter — per-op Python work happens ONLY while profiling."""
    from .. import profiler as _p

    if not _p._running:
        return None
    from .. import telemetry as _tm
    ops_total = _tm.counter(
        "mxtpu_ops_dispatched_total",
        "Imperative op dispatches recorded while profiling",
        labelnames=("op",))

    def _record(name, ts, dur):
        _p.record_op(name, ts, dur)
        ops_total.labels(op=name).inc()

    return (_p._now_us, _record)


class _CaptureScope:
    """Graph-capture hook: while active, every ``invoke`` appends
    ``(op_name, fun, args, kwargs, result)`` — with live NDArrays — to
    ``self.entries``.  Used by the ONNX exporter to lift an imperative
    Gluon forward into a symbolic graph (the deferred-compute analogue of
    `python/mxnet/gluon/block.py:994` `_build_cache`, but for export)."""

    def __init__(self):
        self.entries = []

    def __enter__(self):
        _capture_stack.append(self)
        return self

    def __exit__(self, *exc):
        _capture_stack.pop()
        return False


_capture_stack = []


def _capture_record(name, fun, args, kwargs, res):
    if _capture_stack:
        _capture_stack[-1].entries.append(
            (name or getattr(fun, "__name__", "op"), fun, args, kwargs, res))


def invoke(fun, args, kwargs=None, name=None, differentiable=True, wrap=True):
    """Dispatch ``fun`` (a pure function over jax arrays) imperatively.

    ``args``/``kwargs`` may contain NDArrays anywhere in their pytree
    structure.  When the tape is recording and any float NDArray input is
    attached, the call is executed under ``jax.vjp`` and a Node is recorded.
    """
    kwargs = kwargs or {}
    leaves, treedef = jax.tree_util.tree_flatten((args, kwargs), is_leaf=_is_nd)
    nd_idx = [i for i, leaf in enumerate(leaves) if _is_nd(leaf)]
    datas = list(leaves)
    ctx = None
    for i in nd_idx:
        arr = leaves[i]
        datas[i] = arr._data
        if ctx is None:
            ctx = arr._ctx

    record = (
        differentiable
        and _state.recording
        and any(_attached(leaves[i]) and _is_float(datas[i]) for i in nd_idx)
    )

    prof = _profiler_hook()

    if not record:
        a, kw = jax.tree_util.tree_unflatten(treedef, datas)
        if prof is not None:
            t0 = prof[0]()
            out = fun(*a, **kw)
            prof[1](name or getattr(fun, "__name__", "op"), t0,
                    prof[0]() - t0)
        else:
            out = fun(*a, **kw)
        _naive_sync(out)
        res = _wrap_out(out, ctx, None, name) if wrap else out
        if wrap:
            _capture_record(name, fun, args, kwargs, res)
        return res

    diff_idx = [i for i in nd_idx if _attached(leaves[i]) and _is_float(datas[i])]
    flat_const = list(datas)

    def flat_fun(*diff_datas):
        full = list(flat_const)
        for i, d in zip(diff_idx, diff_datas):
            full[i] = d
        a, kw = jax.tree_util.tree_unflatten(treedef, full)
        return fun(*a, **kw)

    # Fast path for jitted functionals (hybridized blocks): an eager
    # jax.vjp would re-trace the whole program EVERY step (hundreds of ms
    # for a ResNet).  Instead run the cached forward executable now and
    # defer the vjp to backward(), where a jitted fwd+bwd program is
    # compiled once per (fun, structure) and replayed (see _lazy_vjp).
    lazy = isinstance(fun, jax.stages.Wrapped) and _lazy_key(
        fun, treedef, diff_idx, flat_const) is not None
    if lazy:
        if prof is not None:
            t0 = prof[0]()
            out = flat_fun(*[datas[i] for i in diff_idx])
            prof[1](name or getattr(fun, "__name__", "op"), t0,
                    prof[0]() - t0)
        else:
            out = flat_fun(*[datas[i] for i in diff_idx])
        vjp_fn = None
    elif prof is not None:
        t0 = prof[0]()
        out, vjp_fn = jax.vjp(flat_fun, *[datas[i] for i in diff_idx])
        prof[1](name or getattr(fun, "__name__", "op"), t0, prof[0]() - t0)
    else:
        out, vjp_fn = jax.vjp(flat_fun, *[datas[i] for i in diff_idx])
    _naive_sync(out)
    out_leaves, out_treedef = jax.tree_util.tree_flatten(out)
    parents = [
        (leaves[i], leaves[i]._node, getattr(leaves[i], "_node_idx", 0))
        for i in diff_idx
    ]
    structs = [jax.ShapeDtypeStruct(o.shape, o.dtype) for o in out_leaves]
    node = Node(
        name or getattr(fun, "__name__", "op"),
        vjp_fn,
        parents,
        structs,
        out_treedef=out_treedef,
        fun=fun,
        flat_const=flat_const,
        treedef=treedef,
        diff_idx=diff_idx,
    )
    res = _wrap_out(out, ctx, node, name) if wrap else out
    if wrap:
        _capture_record(name, fun, args, kwargs, res)
    return res


def _naive_sync(out):
    """MXNET_ENGINE_TYPE=NaiveEngine: block on every op so async errors
    surface at the faulting call (reference debug engine semantics)."""
    from .. import env as _env

    if _env.is_naive_engine():
        try:
            jax.block_until_ready(out)
        except TypeError:
            pass  # non-array outputs


def _wrap_out(out, ctx, node, name):
    from ..context import current_context

    cls = _ndarray_cls
    if ctx is None:
        ctx = current_context()

    counter = [0]

    def wrap_leaf(x):
        idx = counter[0]
        counter[0] += 1
        if not _is_jax_array(x):
            return x
        arr = cls(x, ctx=ctx)
        if node is not None:
            arr._node = node
            arr._node_idx = idx
        return arr

    if isinstance(out, (jax.Array, onp.ndarray)) or not isinstance(
        out, (tuple, list, dict)
    ):
        return wrap_leaf(out) if _is_jax_array(out) else out
    return jax.tree_util.tree_map(wrap_leaf, out)


def _is_jax_array(x):
    return isinstance(x, (jax.Array, onp.ndarray)) or (
        hasattr(x, "shape") and hasattr(x, "dtype") and not isinstance(x, Node)
    )


# ---------------------------------------------------------------------------
# Backward pass (reference: `Imperative::Backward`, imperative.cc:385)
# ---------------------------------------------------------------------------


def _collect_graph(head_nodes):
    """Reachable nodes + consumer counts (edges node -> parent node)."""
    nodes = set()
    consumers = defaultdict(int)
    stack = list(head_nodes)
    while stack:
        n = stack.pop()
        if n in nodes:
            continue
        nodes.add(n)
        for _arr, pnode, _idx in n.parents:
            if pnode is not None:
                consumers[pnode] += 1
                stack.append(pnode)
    return nodes, consumers


def backward(heads, head_grads=None, retain_graph=False, create_graph=False):
    """Run reverse-mode from ``heads``, writing into leaf ``.grad`` buffers.

    Matches `python/mxnet/autograd.py:245` semantics: ``grad_req='write'``
    overwrites, ``'add'`` accumulates across backward calls; multiple
    contributions within one backward always sum.
    """
    from .. import telemetry as _tm

    with _tm.step_phase("bwd"):
        _accumulate_and_write(
            heads, head_grads, retain_graph, create_graph, variables=None
        )


def grad(heads, variables, head_grads=None, retain_graph=None,
         create_graph=False):
    """Gradients w.r.t. ``variables`` returned (not written to ``.grad``).

    Reference: `python/mxnet/autograd.py:272`.
    """
    if retain_graph is None:
        retain_graph = create_graph
    return _accumulate_and_write(
        heads, head_grads, retain_graph, create_graph, variables=variables
    )


def _accumulate_and_write(heads, head_grads, retain_graph, create_graph,
                          variables):
    cls = _ndarray_cls
    if isinstance(heads, cls):
        heads = [heads]
    if head_grads is None:
        head_grads = [None] * len(heads)
    elif isinstance(head_grads, cls):
        head_grads = [head_grads]
    assert len(heads) == len(head_grads)

    # cotangents per node, indexed by output slot
    node_cts = {}
    leaf_grads = {}  # id(arr) -> (arr, accumulated cotangent)

    def add_leaf(arr, ct):
        key = id(arr)
        if key in leaf_grads:
            prev = leaf_grads[key][1]
            leaf_grads[key] = (arr, _add_ct(prev, ct))
        else:
            leaf_grads[key] = (arr, ct)

    def add_node_ct(node, idx, ct):
        cts = node_cts.setdefault(node, [None] * node.n_outs)
        cts[idx] = ct if cts[idx] is None else _add_ct(cts[idx], ct)

    head_nodes = []
    for h, hg in zip(heads, head_grads):
        if hg is None:
            hg_data = jnp.ones(h.shape, h.dtype)
        else:
            hg_data = hg._data if isinstance(hg, cls) else jnp.asarray(hg)
        if h._node is not None:
            add_node_ct(h._node, h._node_idx, hg_data)
            head_nodes.append(h._node)
        elif _attached(h):
            add_leaf(h, hg_data)

    if not head_nodes and variables is None and not leaf_grads:
        raise ValueError(
            "cannot differentiate: none of the heads is in a recorded graph "
            "(did you forget autograd.record()?)"
        )

    nodes, consumers = _collect_graph(set(head_nodes))
    # Kahn order: a node is ready when all its consumers have propagated.
    ready = [n for n in set(head_nodes)]
    pending = {n: c for n, c in consumers.items()}
    processed = set()
    while ready:
        node = ready.pop()
        if node in processed:
            continue
        processed.add(node)
        cts = node_cts.pop(node, None)
        if cts is None:
            cts = [None] * node.n_outs
        full_cts = [
            ct if ct is not None else jnp.zeros(s.shape, s.dtype)
            for ct, s in zip(cts, node.out_structs)
        ]
        in_grads = _node_vjp(node, full_cts, create_graph)
        if node.parent_versions is not None:
            import warnings
            for (arr, _pn, _pi), v0 in zip(node.parents,
                                           node.parent_versions):
                if v0 is not None and getattr(arr, "_version", v0) != v0:
                    warnings.warn(
                        f"[MXNET_ENGINE_DEBUG] stale read in backward of "
                        f"'{node.name}': an input array was mutated "
                        f"in-place (version {v0} -> {arr._version}) after "
                        f"the op recorded it; the gradient flows to the "
                        f"value read at record time (reference versioned-"
                        f"var semantics), not the current contents",
                        stacklevel=2)
        for (arr, pnode, pidx), g in zip(node.parents, in_grads):
            if pnode is not None:
                from .sparse_grad import RowSparseCT

                if isinstance(g, RowSparseCT):
                    # sparse cotangents exist only for leaf params; an
                    # interior node needs the dense form to keep flowing
                    g = g.to_dense()
                add_node_ct(pnode, pidx, g)
                pending[pnode] -= 1
                if pending[pnode] == 0:
                    ready.append(pnode)
            else:
                add_leaf(arr, g)
        if not retain_graph:
            node.vjp_fn = None
            node.fun = None
            node.flat_const = None

    from .sparse_grad import RowSparseCT

    if variables is not None:
        out = []
        for v in variables:
            entry = leaf_grads.get(id(v))
            g = entry[1] if entry is not None else jnp.zeros(v.shape, v.dtype)
            if isinstance(g, RowSparseCT):
                out.append(_sparse_ct_to_nd(g, v))  # already a container
            else:
                out.append(_as_nd(g, v._ctx, create_graph))
        return out

    # write into .grad honoring grad_req
    for arr, g in leaf_grads.values():
        if arr._grad is None or arr._grad_req == "null":
            continue
        if isinstance(g, RowSparseCT) or _is_row_sparse(arr._grad):
            _write_sparse_grad(arr, g)
            continue
        g_nd = _as_nd(g, arr._ctx, create_graph)
        if arr._grad_req == "add":
            arr._grad._rebind((arr._grad._data + _raw(g_nd)))
        else:
            arr._grad._rebind(_raw(g_nd))
    return None


def _is_row_sparse(x):
    from ..ndarray.sparse import RowSparseNDArray

    return isinstance(x, RowSparseNDArray)


def _sparse_ct_to_nd(ct, v):
    from ..ndarray.sparse import RowSparseNDArray

    r = ct.reduced()
    return RowSparseNDArray(r.values, r.indices, r.shape)


def _write_sparse_grad(arr, g):
    """Write/accumulate into a row_sparse gradient buffer in place
    (reference: row_sparse grad_req handling in `ndarray.cc` CopyFromTo /
    the sparse kUpdate path).  Falls back to densifying when the buffer is
    dense but the cotangent arrived sparse."""
    from .sparse_grad import RowSparseCT
    from ..ndarray.sparse import RowSparseNDArray

    buf = arr._grad
    if not isinstance(buf, RowSparseNDArray):
        dense = g.to_dense() if isinstance(g, RowSparseCT) else _raw(g)
        if arr._grad_req == "add":
            buf._rebind(buf._data + dense)
        else:
            buf._rebind(dense)
        return
    if isinstance(g, RowSparseCT):
        if arr._grad_req == "add" and buf.indices.size:
            merged = RowSparseCT(
                jnp.concatenate([jnp.asarray(buf.indices), g.indices]),
                jnp.concatenate([jnp.asarray(buf.data), g.values]),
                g.shape).reduced()
        else:
            merged = g.reduced()
        buf._set_rows(merged.indices, merged.values)
    else:
        # dense cotangent into a sparse buffer: keep only nonzero rows
        dense = _raw(g)
        if arr._grad_req == "add" and buf.indices.size:
            dense = dense.at[jnp.asarray(buf.indices)].add(
                jnp.asarray(buf.data))
        nz = jnp.nonzero(jnp.any(dense.reshape(dense.shape[0], -1) != 0,
                                 axis=1))[0].astype(jnp.int32)
        buf._set_rows(nz, dense[nz])


def _raw(x):
    return x._data if _is_nd(x) else x


def _as_nd(g, ctx, keep_node=False):
    if _is_nd(g):
        return g
    arr = _ndarray_cls(g, ctx=ctx)
    return arr


def _add_ct(a, b):
    from .sparse_grad import RowSparseCT, add_cts

    if isinstance(a, RowSparseCT) or isinstance(b, RowSparseCT):
        return add_cts(a, b)
    if _is_nd(a) or _is_nd(b):
        return invoke(jnp.add, (a, b), name="_backward_add")
    return a + b


def _lazy_key(fun, treedef, diff_idx, flat_const):
    """Cache key for a deferred-vjp executor, or None if any static (non
    array) leaf is unhashable."""
    diff = set(diff_idx)
    statics = []
    for i, v in enumerate(flat_const):
        if i in diff or isinstance(v, (jax.Array, onp.ndarray)):
            continue
        try:
            hash(v)
        except TypeError:
            return None
        statics.append((i, v))
    return (id(fun), treedef, tuple(diff_idx), tuple(statics))


# (fun, structure) -> (jitted fwd+bwd executor, fun ref keeping the id
# stable).  Bounded: evicts oldest (compiled executables are heavy).
_VJP_EXEC_CACHE = {}
_VJP_EXEC_CACHE_MAX = 256


def evict_vjp_cache_for(fun):
    """Drop deferred-vjp executors built over ``fun``.  The executor's
    closure holds ``fun`` (for a hybridized block: the block and all its
    parameter buffers), so HybridBlock._clear_cached calls this to avoid
    pinning dropped models in device memory."""
    fid = id(fun)
    for key in [k for k in _VJP_EXEC_CACHE if k[0] == fid]:
        del _VJP_EXEC_CACHE[key]


def _lazy_vjp(node, ct):
    """Backward for a node recorded through the lazy fast path: one jitted
    program recomputes the forward and applies the vjp — compiled once per
    (fun, structure), replayed every subsequent step.  This is the tape's
    CachedOp::Backward analogue (`src/imperative/cached_op.h:637`)."""
    key = _lazy_key(node.fun, node.treedef, node.diff_idx, node.flat_const)
    entry = _VJP_EXEC_CACHE.get(key)
    if entry is None:
        fun, treedef = node.fun, node.treedef
        diff_idx = tuple(node.diff_idx)
        n_leaves = len(node.flat_const)
        diff = set(diff_idx)
        arr_pos = tuple(
            i for i, v in enumerate(node.flat_const)
            if i not in diff and isinstance(v, (jax.Array, onp.ndarray)))
        static = {i: v for i, v in enumerate(node.flat_const)
                  if i not in diff and i not in arr_pos}

        def exec_raw(diff_datas, const_datas, ct_val):
            full = [None] * n_leaves
            for i, v in static.items():
                full[i] = v
            for i, v in zip(arr_pos, const_datas):
                full[i] = v

            def ff(*dd):
                leaves = list(full)
                for i, d in zip(diff_idx, dd):
                    leaves[i] = d
                a, kw = jax.tree_util.tree_unflatten(treedef, leaves)
                return fun(*a, **kw)

            _out, vjp_fn = jax.vjp(ff, *diff_datas)
            return vjp_fn(ct_val)

        entry = (jax.jit(exec_raw), fun)
        if len(_VJP_EXEC_CACHE) >= _VJP_EXEC_CACHE_MAX:
            _VJP_EXEC_CACHE.pop(next(iter(_VJP_EXEC_CACHE)))
        _VJP_EXEC_CACHE[key] = entry
    exec_fn = entry[0]
    diff_datas = tuple(node.flat_const[i] for i in node.diff_idx)
    diff = set(node.diff_idx)
    const_datas = tuple(
        v for i, v in enumerate(node.flat_const)
        if i not in diff and isinstance(v, (jax.Array, onp.ndarray)))
    return exec_fn(diff_datas, const_datas, ct)


def _node_vjp(node, cotangents, create_graph):
    """Apply the node's vjp.  With create_graph, re-derive it through invoke
    so the backward computation is itself recorded (higher-order grads;
    reference: `create_graph` in `python/mxnet/autograd.py:272`)."""
    if node.out_treedef is not None:
        ct = jax.tree_util.tree_unflatten(node.out_treedef, list(cotangents))
    else:
        ct = tuple(cotangents)
        if len(node.out_structs) == 1:
            ct = ct[0]
    if not create_graph:
        if node.vjp_fn is None and node.fun is not None:
            return _lazy_vjp(node, ct)
        if node.vjp_fn is None:
            raise RuntimeError(
                "graph has been freed; pass retain_graph=True to backward() "
                "to call it twice"
            )
        return node.vjp_fn(ct)

    # Recompute vjp under the tape: inputs are the parent arrays (possibly
    # themselves recorded), so second-order chains connect.
    fun, flat_const, treedef, diff_idx = (
        node.fun, node.flat_const, node.treedef, node.diff_idx,
    )
    if fun is None:
        if node.vjp_fn is not None:
            # a custom node (e.g. sparse_embedding) that never carried the
            # re-derivable forward — not the freed-graph case
            raise NotImplementedError(
                f"create_graph=True through '{node.name}' is not supported "
                "(higher-order grads need the dense path)")
        raise RuntimeError("graph has been freed; use retain_graph=True")

    def bwd(*xs_and_ct):
        xs = xs_and_ct[: len(diff_idx)]
        ct_in = xs_and_ct[len(diff_idx):]
        if node.out_treedef is not None:
            ct_val = jax.tree_util.tree_unflatten(node.out_treedef, list(ct_in))
        else:
            ct_val = ct_in[0] if len(node.out_structs) == 1 else tuple(ct_in)

        def flat_fun(*diff_datas):
            full = list(flat_const)
            for i, d in zip(diff_idx, diff_datas):
                full[i] = d
            a, kw = jax.tree_util.tree_unflatten(treedef, full)
            return fun(*a, **kw)

        _out, vjp_fn = jax.vjp(flat_fun, *xs)
        return vjp_fn(ct_val)

    inputs = [arr for arr, _pn, _pi in node.parents]
    ct_list = list(cotangents)
    res = invoke(bwd, tuple(inputs) + tuple(ct_list), name=f"_backward_{node.name}")
    if not isinstance(res, (tuple, list)):
        res = (res,)
    return res
