"""Gluon Block / HybridBlock.

Reference: `python/mxnet/gluon/block.py` — `Block` (:202, child registry,
param collection, hooks, save/load), `HybridBlock` (:860, deferred-compute
tracing `_build_cache`:994 → CachedOp:1085).

TPU-native design: ``hybridize()`` does not build an nnvm CachedOp — it
wraps a *functional* forward (parameters passed as arguments, param access
redirected through a trace-scope override) in ``jax.jit``:

* shape-keyed recompilation = the reference's per-signature
  `SetForwardGraph` re-inference (`cached_op.cc:168-234`);
* XLA fusion/memory planning = `MXPlanMemory` + pointwise fusion for free;
* under ``autograd.record`` the whole compiled program becomes ONE tape node
  via `jax.vjp` — forward is one XLA executable, backward another (the
  CachedOp backward graph equivalent);
* randomness: a fresh PRNG key is an *argument* per call (no baked-in
  constants), threaded to dropout etc. through `random.key_stream_scope`;
* BatchNorm moving stats: traced updates are extra outputs written back
  after execution (`ops/aux_scope.py`) — the engine-write-var analogue.
"""
from __future__ import annotations

import contextlib
import re

import jax
import numpy as onp

from ..base import MXNetError
from ..context import Context, current_context
from ..ndarray.ndarray import NDArray
from ..ops.invoke import (invoke, is_training, set_recording,
                          set_training, is_backward_expected,
                          set_backward_expected)
from ..ops.aux_scope import aux_update_scope
from .. import initializer as _initializer
from .. import random as _rng
from .. import telemetry as _telemetry
from .parameter import Parameter, DeferredInitializationError, _param_override_scope

__all__ = ["Block", "HybridBlock", "SymbolBlock"]


def _is_nd(x):
    return isinstance(x, NDArray)


def _first_nd(items):
    """The first NDArray found in items (one level of list/tuple nesting,
    covering RNN-style state lists)."""
    for a in items:
        if _is_nd(a):
            return a
        if isinstance(a, (list, tuple)):
            for b in a:
                if _is_nd(b):
                    return b
    return None


def _first_ctx(items):
    """Context of the first NDArray found in items."""
    first = _first_nd(items)
    return None if first is None else first.ctx


def _trace_span(block):
    """The span of one block's ``forward`` while jax traces it: a step's
    (or a hybridized block's) trace split by Gluon block on the span record.
    Shorter than the ``xla.trace`` floor it is not recorded, and its time
    stays in the enclosing block's self time."""
    return _telemetry.trace_span("block.trace", block=block.name,
                                 cls=type(block).__name__)


_NO_SPAN = contextlib.nullcontext()


class Block:
    """Base building block (reference `block.py:202`)."""

    def __init__(self):
        self._children = {}
        self._reg_params = {}
        self._forward_hooks = []
        self._forward_pre_hooks = []
        self._scope_name = None

    # -- attribute registration (reference `__setattr__`, block.py) -------
    def __setattr__(self, name, value):
        if isinstance(value, Block):
            existing = self.__dict__.get("_children")
            if existing is not None:
                existing[name] = value
                # the attribute name IS the layer's identity everywhere
                # else (param structure names, repr); stamp it as the
                # name-scope too so HLO op metadata matches
                # `collect_params` naming (tools/layerscope buckets by it)
                value._scope_name = name
        elif isinstance(value, Parameter):
            existing = self.__dict__.get("_reg_params")
            if existing is not None:
                existing[name] = value
        super().__setattr__(name, value)

    # -- parameter collection ---------------------------------------------
    def _collect_params_with_prefix(self, prefix=""):
        if prefix:
            prefix += "."
        ret = {prefix + key: val for key, val in self._reg_params.items()}
        for name, child in self._children.items():
            ret.update(child._collect_params_with_prefix(prefix + name))
        return ret

    def collect_params(self, select=None):
        ret = {}
        for name, param in self._collect_params_with_prefix().items():
            param._structure_name = name
            if select is None or re.match(select, name):
                ret[name] = param
        return ret

    @property
    def params(self):
        return dict(self._reg_params)

    @property
    def name(self):
        """Scope name: the attribute name this block was registered under
        in its parent (matching its parameter structure-name prefix), or
        the class name for an unparented root.  This is the component
        `jax.named_scope` pushes around ``forward`` so compiled-HLO op
        metadata carries the block hierarchy (see
        `mxnet_tpu/analysis/census.py`)."""
        return self._scope_name or type(self).__name__

    @property
    def children(self):
        """Name -> direct child Block mapping (public iteration surface;
        tooling like Monitor walks this instead of `_children`)."""
        return dict(self._children)

    # -- partition-rule collection (parallel.recipe) -----------------------
    def collect_partition_rules(self, axes, prefix=""):
        """Gather per-block ``partition_rules()`` over the child tree,
        anchored at each block's parameter structure path — the rule
        source a :class:`~mxnet_tpu.parallel.ShardingRecipe` merges with
        user overrides.

        ``axes`` is the set of mesh axis names the recipe provides.  A
        block exposing ``partition_rules(axis_name=..., prefix=...)``
        (MoEFFN, GPipeMLP, nn.Dense, MultiHeadAttention, ...) contributes
        its rules when its default ``axis_name`` is in ``axes``; a block
        whose axis is absent (an MoE layer under a dp.tp recipe with no
        ``ep``) contributes nothing and its params fall through to
        replicated.  Traversal is pre-order — a parent's rules precede
        its children's, so a composite layer that knows its children's
        roles (MultiHeadAttention marking ``proj`` row-parallel) wins
        over the child's generic default (Dense's column-parallel) under
        first-match-wins.
        """
        import inspect

        axes = set(axes)
        rules = []
        fn = getattr(type(self), "partition_rules", None)
        if callable(fn):
            try:
                axis = inspect.signature(fn).parameters["axis_name"].default
            except (KeyError, ValueError):
                axis = None
            if axis in axes:
                anchor = ("^" + re.escape(prefix) + r"\.") if prefix \
                    else "^"
                rules += list(fn(axis_name=axis, prefix=anchor))
        for name, child in self._children.items():
            child_prefix = f"{prefix}.{name}" if prefix else name
            rules += child.collect_partition_rules(axes, child_prefix)
        return rules

    # -- lifecycle ---------------------------------------------------------
    def initialize(self, init=None, ctx=None, verbose=False,
                   force_reinit=False):
        if init is None:
            init = _initializer.Uniform()
        params = self.collect_params()
        # one span per call: the loop is flat over the whole tree, so no
        # child's `initialize` runs inside it
        with _telemetry.span("block.initialize", block=self.name,
                             params=len(params)):
            for _name, param in params.items():
                param.initialize(init=param.init, ctx=ctx,
                                 default_init=init,
                                 force_reinit=force_reinit)

    def hybridize(self, active=True, **kwargs):
        for child in self._children.values():
            child.hybridize(active, **kwargs)

    def cast(self, dtype):
        for child in self._children.values():
            child.cast(dtype)
        for param in self._reg_params.values():
            if onp.dtype(param.dtype).kind == "f" or str(param.dtype) == "bfloat16":
                param.cast(dtype)

    def apply(self, fn):
        for child in self._children.values():
            child.apply(fn)
        fn(self)
        return self

    def reset_ctx(self, ctx):
        for param in self.collect_params().values():
            param.reset_ctx(ctx)

    reset_device = reset_ctx

    def zero_grad(self):
        for param in self.collect_params().values():
            param.zero_grad()

    # -- hooks -------------------------------------------------------------
    def register_forward_pre_hook(self, hook):
        self._forward_pre_hooks.append(hook)
        return _HookHandle(self._forward_pre_hooks, hook)

    def register_forward_hook(self, hook):
        self._forward_hooks.append(hook)
        return _HookHandle(self._forward_hooks, hook)

    # -- save / load (reference block.py:340,376) ---------------------------
    def save_parameters(self, filename, deduplicate=False):
        params = self._collect_params_with_prefix()
        arg_dict = {}
        seen = {}
        for name, param in params.items():
            if param._data is None:
                continue
            arr = param.data()
            if deduplicate and id(param) in seen:
                continue
            seen[id(param)] = name
            arg_dict[name] = arr
        from ..utils.serialization import save_ndarrays
        save_ndarrays(filename, arg_dict)

    def load_parameters(self, filename, ctx=None, allow_missing=False,
                        ignore_extra=False, cast_dtype=False,
                        dtype_source="current"):
        from ..utils.serialization import load_ndarrays
        loaded = load_ndarrays(filename, ctx=ctx)
        # Module-era checkpoints (reference `model.py save_checkpoint`)
        # prefix names with "arg:"/"aux:"; reference load_parameters
        # strips them (`python/mxnet/gluon/block.py:376`)
        loaded = {k.split(":", 1)[1] if k.startswith(("arg:", "aux:"))
                  else k: v for k, v in loaded.items()}
        params = self._collect_params_with_prefix()
        for name, param in params.items():
            if name not in loaded:
                if not allow_missing:
                    raise AssertionError(
                        f"Parameter '{name}' is missing in '{filename}'")
                continue
            value = loaded[name]
            if cast_dtype:
                value = value.astype(param.dtype)
            if ctx is not None:
                param.reset_ctx(ctx)
            param.set_data(value)
        if not ignore_extra:
            extra = set(loaded) - set(params)
            if extra:
                raise AssertionError(
                    f"Parameters {sorted(extra)} in file '{filename}' are "
                    "not present in this Block")

    def load_dict(self, param_dict, ctx=None, allow_missing=False,
                  ignore_extra=False, cast_dtype=False):
        params = self._collect_params_with_prefix()
        for name, param in params.items():
            if name in param_dict:
                param.set_data(param_dict[name])
            elif not allow_missing:
                raise AssertionError(f"Parameter '{name}' missing")

    # -- call ---------------------------------------------------------------
    def __call__(self, *args, **kwargs):
        for hook in self._forward_pre_hooks:
            hook(self, args)
        # classic multi-device data parallelism: parameters resolve their
        # per-context copy through current_context(), so scope it to the
        # input's context (the reference dispatches kernels by data ctx)
        first = _first_nd(args)
        if first is None:
            first = _first_nd(kwargs.values())
        in_ctx = None if first is None else first.ctx
        # name-scope the forward so ops traced inside land in HLO
        # metadata as "<parent>/<name>/<op>" — the census
        # (mxnet_tpu/analysis/census.py) buckets compiled cost by these
        # paths.  Outside a trace this is a thread-local push/pop, and the
        # test on the input is all the span record costs an eager call.
        traced = first is not None and isinstance(first._data, jax.core.Tracer)
        with jax.named_scope(self.name), \
                _trace_span(self) if traced else _NO_SPAN:
            if in_ctx is not None and in_ctx != current_context():
                with in_ctx:
                    out = self.forward(*args, **kwargs)
            else:
                out = self.forward(*args, **kwargs)
        for hook in self._forward_hooks:
            hook(self, args, out)
        return out

    def forward(self, *args, **kwargs):  # pragma: no cover - abstract
        raise NotImplementedError

    def as_endpoint(self, **serve_kwargs):
        """Expose this block as a batched inference service
        (:class:`mxnet_tpu.serve.Endpoint`): a bounded request queue, a
        shape-bucketed dynamic micro-batcher, and an executable cache
        that keeps steady-state traffic retrace-free.  The endpoint
        runs the block in predict mode on its current parameters::

            ep = net.as_endpoint(max_batch_size=16, max_latency_ms=5)
            ep.warmup(example_batch)
            future = ep.submit(request)

        Keyword arguments are forwarded to ``Endpoint``.
        """
        from ..serve import Endpoint
        return Endpoint(self, **serve_kwargs)

    def summary(self, *inputs):
        """Print a per-layer summary (reference block.py `summary`)."""
        lines = []

        def walk(block, prefix):
            pcount = sum(int(onp.prod(p.shape)) for p in
                         block._reg_params.values() if p._shape_known())
            lines.append(f"{prefix}{type(block).__name__}: {pcount} params")
            for name, child in block._children.items():
                walk(child, prefix + "  ")

        walk(self, "")
        total = sum(int(onp.prod(p.shape)) for p in
                    self.collect_params().values() if p._shape_known())
        lines.append(f"Total params: {total}")
        print("\n".join(lines))

    def __repr__(self):
        children = "\n".join(
            f"  ({name}): {repr(child).splitlines()[0]}"
            for name, child in self._children.items())
        return f"{type(self).__name__}(\n{children}\n)" if children else \
            f"{type(self).__name__}()"


class _HookHandle:
    def __init__(self, collection, hook):
        self._collection = collection
        self._hook = hook

    def detach(self):
        if self._hook in self._collection:
            self._collection.remove(self._hook)


class HybridBlock(Block):
    """Block whose forward can be compiled to one XLA program
    (reference `block.py:860`)."""

    def __init__(self):
        super().__init__()
        self._active = False
        self._jit_flags = {}
        self._jit_cache = {}      # (training, backward) -> jitted functional
        self._cached_param_list = None
        self._aux_param_holder = []

    def hybridize(self, active=True, static_alloc=False, static_shape=False,
                  inline_limit=None, backend=None, **kwargs):
        """Compile the forward with XLA.  ``static_alloc``/``static_shape``
        map to buffer donation / single-signature assumptions and are
        accepted for compatibility (XLA plans memory either way,
        `cached_op.h:413-432` in the reference)."""
        self._active = active
        self._jit_flags = dict(static_alloc=static_alloc,
                               static_shape=static_shape)
        self._clear_cached()
        super().hybridize(active=False)  # children run inside this trace

    def _clear_cached(self):
        from ..ops.invoke import evict_vjp_cache_for
        for fn in self._jit_cache.values():
            evict_vjp_cache_for(fn)
        self._jit_cache = {}
        self._cached_param_list = None

    def optimize_for(self, x, *args, backend=None, clear=True, **kwargs):
        """Reference `block.py:1142`: partition/optimize for a backend.  The
        XLA analogue: hybridize + warm the jit cache with this input."""
        self.hybridize(True)
        out = self(x, *args)
        if isinstance(out, NDArray):
            out.wait_to_read()
        return out

    def cast(self, dtype):
        self._clear_cached()
        super().cast(dtype)

    # -- deferred shape inference ------------------------------------------
    def _ensure_shapes(self, *args):
        params = self.collect_params()
        pending = [p for p in params.values() if p._deferred_init is not None]
        if not pending:
            return
        # one eager forward infers shapes & finishes deferred init
        # (reference: deferred compute's shape inference, block.py:994);
        # it compiles a small program a shape, so set-up pays for it
        prev_rec = set_recording(False)
        try:
            with _telemetry.span("block.settle_shapes", cat="setup",
                                 block=self.name, pending=len(pending)):
                self.forward(*args)
        finally:
            set_recording(prev_rec)

    # -- the compiled path --------------------------------------------------
    def _build_functional(self, training, backward):
        block = self
        holder = self._aux_param_holder

        def functional(param_datas, key, flat_inputs, treedef_id):
            # runs only at trace time (jit caches by shape after that)
            out_datas, aux = _scoped_forward(
                block, block._cached_param_list, param_datas, key,
                flat_inputs, _TREEDEFS[treedef_id], training,
                backward=backward)
            holder.clear()
            holder.extend(getattr(a, "_param_ref", None)
                          for a, _v in aux.updates)
            aux_datas = [v._data if _is_nd(v) else v for _a, v in aux.updates]
            return out_datas, aux_datas

        return jax.jit(functional, static_argnums=(3,))

    def _call_cached(self, *args):
        if self._cached_param_list is None:
            self._ensure_shapes(*args)
            params = self.collect_params()
            self._cached_param_list = [params[k] for k in sorted(params)]
        plist = self._cached_param_list
        training = is_training()
        # a predict-mode tape (autograd.record(train_mode=False)) still
        # backprops through the cached program: trace-time policy must
        # know, and the program differs, so it keys the cache too.
        # is_backward_expected() also carries the flag across an
        # enclosing trace (which forces recording off) into a nested
        # active HybridBlock.
        backward = is_backward_expected()  # ORs in recording + training
        jit_fn = self._jit_cache.get((training, backward))
        if jit_fn is None:
            jit_fn = self._build_functional(training, backward)
            self._jit_cache[(training, backward)] = jit_fn

        flat, treedef = jax.tree_util.tree_flatten(args, is_leaf=_is_nd)
        treedef_id = _intern_treedef(treedef)
        param_nds = [p.data() for p in plist]
        key = _rng.new_key()

        out, aux_vals = invoke(
            jit_fn, (param_nds, key, flat, treedef_id),
            name=f"{type(self).__name__}.hybrid_forward")
        # retrace watchdog: a steady-state recompile of the hybridized
        # program (shape drift past warmup) is the bug class serving
        # buckets exist to prevent — count it and warn
        _telemetry.watchdog().observe(
            jit_fn, name=f"{type(self).__name__}.hybrid_forward",
            scope_root=self.name)
        # write deferred aux updates (BatchNorm moving stats) back
        for p, v in zip(self._aux_param_holder, aux_vals):
            if p is not None:
                p.data()._rebind(v._data if _is_nd(v) else v)
        return out

    def __call__(self, *args, **kwargs):
        if self._active and not kwargs:
            for hook in self._forward_pre_hooks:
                hook(self, args)
            # as Block.__call__: multi-device parameters (and the aux
            # write-back) resolve their copy through current_context()
            in_ctx = _first_ctx(args)
            if in_ctx is not None and in_ctx != current_context():
                with in_ctx:
                    out = self._call_cached(*args)
            else:
                out = self._call_cached(*args)
            for hook in self._forward_hooks:
                hook(self, args, out)
            return out
        return super().__call__(*args, **kwargs)

    def export(self, path, epoch=0, remove_amp_cast=True, example_args=None):
        """Serialize the model for deployment (reference block.py:1300:
        symbol JSON + params).  The TPU-native graph format is serialized
        StableHLO via ``jax.export``: ``{path}-symbol.bin`` holds the
        compiled inference program, ``{path}-symbol.json`` its signature,
        and ``{path}-{epoch:04d}.params`` the parameters —
        `SymbolBlock.imports` reloads all three without the python class.

        Exporting the program requires ``example_args`` (or a previously
        traced call) to fix input shapes/dtypes, like the reference's
        shape-specialized symbol graphs.
        """
        import json as _json

        if example_args is not None:
            self._ensure_shapes(*example_args)
        fname = f"{path}-{epoch:04d}.params"
        self.save_parameters(fname)

        if example_args is None:
            return fname, None
        params = self.collect_params()
        # only initialized params enter the graph (save_parameters skips
        # the rest too; a registered-but-unused deferred param must not
        # break export)
        names = [k for k in sorted(params) if params[k]._data is not None]
        plist = [params[k] for k in names]
        block = self

        flat_in, in_treedef = jax.tree_util.tree_flatten(
            example_args, is_leaf=_is_nd)
        if not all(_is_nd(a) for a in flat_in):
            raise TypeError("example_args must contain only NDArrays "
                            "(arbitrarily nested)")

        def infer_fn(param_datas, *input_datas):
            # deployment graph: predict mode, fixed key (dropout inactive)
            out_datas, _aux = _scoped_forward(
                block, plist, param_datas, jax.random.key(0),
                list(input_datas), in_treedef, training=False)
            return out_datas

        from jax import export as jexport

        param_specs = tuple(
            jax.ShapeDtypeStruct(p.data()._data.shape, p.data()._data.dtype)
            for p in plist)
        input_specs = tuple(
            jax.ShapeDtypeStruct(a._data.shape, a._data.dtype)
            for a in flat_in)
        # lower for both CPU and TPU so an artifact exported on a dev
        # machine still runs on the deployment chip
        exported = jexport.export(
            jax.jit(infer_fn),
            platforms=("cpu", "tpu"))(param_specs, *input_specs)
        with open(f"{path}-symbol.bin", "wb") as f:
            f.write(exported.serialize())
        meta = {
            "format": "mxnet_tpu-stablehlo-v1",
            "param_names": names,
            "inputs": [{"shape": list(a.shape), "dtype": str(a.dtype)}
                       for a in flat_in],
        }
        with open(f"{path}-symbol.json", "w") as f:
            _json.dump(meta, f, indent=1)
        return fname, f"{path}-symbol.bin"

    def infer_shape(self, *args):
        self._ensure_shapes(*args)


def _scoped_forward(block, plist, param_datas, key, flat_inputs, treedef,
                    training, backward=None):
    """Run ``block.forward`` with parameters overridden by ``param_datas``
    under the shared trace-scope protocol (override scope + key stream +
    aux capture) — used by both the hybridize jit path and `export`.
    Returns (out_datas, aux).

    ``backward`` tells trace-time policy code (e.g. the flash-attention
    auto crossover) whether a backward pass will run through the traced
    program — recording is forced off during the trace, so the tape flag
    cannot carry that information itself.  Defaults to ``training``."""
    mapping = {}
    for p, d in zip(plist, param_datas):
        nd = NDArray(d)
        nd._param_ref = p
        mapping[id(p)] = nd
    wrapped = [NDArray(d) for d in flat_inputs]
    args = jax.tree_util.tree_unflatten(treedef, wrapped)
    prev_rec = set_recording(False)
    prev_tr = set_training(training)
    prev_bwd = set_backward_expected(
        training if backward is None else backward)
    try:
        with _param_override_scope(mapping), _rng.key_stream_scope(key), \
                aux_update_scope() as aux, jax.named_scope(block.name), \
                _trace_span(block):
            out = block.forward(*args)
    finally:
        set_recording(prev_rec)
        set_training(prev_tr)
        set_backward_expected(prev_bwd)
    out_datas = jax.tree_util.tree_map(
        lambda o: o._data if _is_nd(o) else o, out, is_leaf=_is_nd)
    return out_datas, aux


# treedefs are hashable but not weak-refable; intern them for
# static_argnums.  Keyed by the treedef ITSELF (equality), not hash(td):
# a hash collision between two structures must map to two ids, or a
# compiled program would silently reinterpret its inputs.
_TREEDEFS = {}           # id -> treedef
_TREEDEF_IDS = {}        # treedef -> id


def _intern_treedef(td):
    key = _TREEDEF_IDS.get(td)
    if key is None:
        key = len(_TREEDEFS)
        _TREEDEF_IDS[td] = key
        _TREEDEFS[key] = td
    return key


class SymbolBlock(Block):
    """Reference `block.py:1500` — runs a serialized graph without its
    python class.  The graph format is serialized StableHLO written by
    `HybridBlock.export(..., example_args=...)`; `imports` reloads the
    program and parameters and yields a callable block."""

    def __init__(self, exported, param_names, param_datas):
        super().__init__()
        self._exported = exported
        self._param_names = param_names
        self._param_datas = list(param_datas)

    @staticmethod
    def imports(symbol_file, input_names=None, param_file=None, ctx=None):
        """Load `{prefix}-symbol.json` (+`.bin`) and params (reference
        block.py:1532).  `symbol_file` may be the json path or the prefix."""
        import json as _json

        from jax import export as jexport

        prefix = symbol_file
        for suffix in ("-symbol.json", "-symbol.bin"):
            if prefix.endswith(suffix):
                prefix = prefix[: -len(suffix)]
        with open(f"{prefix}-symbol.json") as f:
            meta = _json.load(f)
        if meta.get("format") != "mxnet_tpu-stablehlo-v1":
            raise ValueError(f"unknown export format {meta.get('format')!r}")
        with open(f"{prefix}-symbol.bin", "rb") as f:
            exported = jexport.deserialize(f.read())
        names = meta["param_names"]
        if param_file is None:
            import glob as _glob

            cands = sorted(_glob.glob(f"{_glob.escape(prefix)}-*.params"))
            if not cands:
                raise FileNotFoundError(f"no params found for {prefix}")
            param_file = cands[-1]
        from ..utils.serialization import load_ndarrays

        loaded = load_ndarrays(param_file)
        datas = [loaded[n]._data for n in names]
        return SymbolBlock(exported, names, datas)

    def forward(self, *args):
        flat, _treedef = jax.tree_util.tree_flatten(args, is_leaf=_is_nd)
        datas = tuple(a._data if _is_nd(a) else a for a in flat)
        out = self._exported.call(tuple(self._param_datas), *datas)
        return jax.tree_util.tree_map(NDArray, out)
