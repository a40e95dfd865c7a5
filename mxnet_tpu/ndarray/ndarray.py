"""The user-facing tensor.

Reference: `include/mxnet/ndarray.h:82` (``NDArray`` over a ref-counted
``Chunk`` holding a ``Storage::Handle`` + engine var) and the python mirror
`python/mxnet/numpy/multiarray.py`.

TPU-native design: the Chunk is a ``jax.Array`` (a PjRt buffer).  The engine
"variable" that orders reads/writes in the reference is the buffer's XLA
definition event — PjRt already sequences compute per device and exposes
``block_until_ready`` (== ``WaitToRead``).  Mutation (`a += b`, sliced
assignment, optimizer updates) re-binds this wrapper to a fresh buffer and
bumps ``_version`` — the reference's var/version pair (`ndarray.h:401-410`).
Inside a ``jax.jit`` trace ``_data`` is a tracer, which is how ``hybridize()``
traces Gluon blocks without a separate deferred-compute mode
(`src/imperative/imperative.cc:40` in the reference).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as onp

from ..base import MXNetError, integer_types, numeric_types
from ..context import Context, current_context
from ..ops import invoke as _iv
from ..ops.invoke import invoke

__all__ = ["NDArray", "array", "empty", "from_jax", "waitall"]

# Large-tensor stance (reference builds with USE_INT64_TENSOR_SIZE,
# CMakeLists.txt:84 region; nightly fence tests/nightly/test_large_array.py):
# arrays may exceed 2^31 elements — XLA tracks shapes/sizes in 64 bits,
# so creation, elementwise ops, reductions, and slices STARTING below
# the boundary (any length) work above it (fenced by
# tests/test_large_tensor.py on the host backend; 16 GB HBM bounds
# TPU-resident arrays to ~the boundary for int8/bf16 anyway).  What
# cannot cross 2^31 is a POSITION operand — an element index or slice
# start: jax runs in 32-bit index mode, where gather would
# OverflowError deep in dispatch and scatter SILENTLY DROPS writes on
# any >2^31-element operand, so NDArray indexing raises this IndexError
# up front instead.  Arithmetic dtypes cap at 32 bits in the same mode
# (an int64 compute request truncates to int32 with a jax warning) —
# 64-bit here means sizes/shapes, not accumulator width; use f32/f64
# accumulation for boundary-crossing reductions.
_INT64_INDEX_MSG = (
    "index position beyond 2^31-1 is not supported (32-bit index mode); "
    "whole-array ops, below-boundary slice starts, and contiguous-slice "
    "ASSIGNMENT (lowered to static slice+concat, no scatter) on "
    ">2^31-element arrays ARE supported — see tests/test_large_tensor.py "
    "for the boundary contract")

# Element-count ceiling above which __setitem__ refuses jax's scatter
# lowering (32-bit scatter indices silently drop the write there) and
# instead requires the scatter-free slice+concat plan.  Module constant
# so tests can shrink it and exercise the big-array path on small
# arrays.
_SETITEM_SCATTER_LIMIT = 2 ** 31 - 1


class NDArray:
    _slots = (
        "_data",
        "_ctx",
        "_grad",
        "_grad_req",
        "_node",
        "_node_idx",
        "_version",
    )

    def __init__(self, data, ctx=None, dtype=None):
        if isinstance(data, NDArray):
            ctx = ctx or data._ctx
            data = data._data
        if dtype is not None:
            dtype = onp.dtype(dtype) if not isinstance(data, jax.core.Tracer) else dtype
        if isinstance(data, jax.core.Tracer):
            self._data = data if dtype is None else data.astype(dtype)
            self._ctx = Context(ctx) if ctx is not None else current_context()
        else:
            if ctx is None:
                ctx = current_context()
            else:
                ctx = Context(ctx)
            if isinstance(data, jax.Array):
                self._data = data if dtype is None else data.astype(dtype)
            else:
                with jax.default_device(ctx.jax_device()):
                    self._data = jnp.asarray(data, dtype=dtype)
            self._ctx = ctx
        self._grad = None
        self._grad_req = "null"
        self._node = None
        self._node_idx = 0
        self._version = 0

    # ------------------------------------------------------------------
    # chunk / engine surface
    # ------------------------------------------------------------------
    @property
    def data(self):
        """The underlying jax.Array (or tracer during hybridize tracing)."""
        return self._data

    def _rebind(self, new_data, node=None, node_idx=0):
        """Mutate in place: point this NDArray at a new buffer.

        The reference performs true in-place writes through engine write-vars;
        on XLA the buffer is immutable so mutation is re-binding + version
        bump (safe for the tape, see `ops/invoke.py`)."""
        if isinstance(new_data, NDArray):
            node = new_data._node
            node_idx = new_data._node_idx
            new_data = new_data._data
        self._data = new_data
        self._node = node
        self._node_idx = node_idx
        self._version += 1
        return self

    @property
    def version(self):
        """Mutation counter (reference `NDArray::version`,
        `ndarray.h:401-410`): bumps on every in-place write/rebind."""
        return self._version

    def wait_to_read(self):
        """Block until the buffer is defined (reference ``WaitToRead``);
        asynchronous execution errors are raised here, matching the
        reference's contract (`src/engine/threaded_engine.h:461-498`)."""
        if isinstance(self._data, jax.Array):
            self._data.block_until_ready()
        return self

    wait_to_write = wait_to_read

    def prefetch_to(self, ctx):
        """Start an asynchronous copy of this array to ``ctx`` and return
        the destination NDArray immediately (reference role:
        `src/io/iter_prefetcher.h:1` / DataLoader ``pin_memory``).

        The returned array's buffer is in flight; any computation consuming
        it is ordered by PjRt after the transfer completes, so issuing
        ``prefetch_to`` for batch N+1 before dispatching step N overlaps
        the H2D wire time with device compute."""
        from ..context import Context
        c = Context(ctx)
        return NDArray(jax.device_put(self._data, c.jax_device()), ctx=c)

    # ------------------------------------------------------------------
    # basic properties
    # ------------------------------------------------------------------
    @property
    def shape(self):
        return tuple(self._data.shape)

    @property
    def dtype(self):
        return onp.dtype(self._data.dtype)

    @property
    def size(self):
        s = 1
        for d in self.shape:
            s *= d
        return s

    @property
    def ndim(self):
        return self._data.ndim

    @property
    def ctx(self):
        return self._ctx

    @property
    def context(self):
        return self._ctx

    @property
    def device(self):
        return self._ctx

    @property
    def T(self):
        return invoke(jnp.transpose, (self,), name="transpose")

    def __len__(self):
        if self.ndim == 0:
            raise TypeError("len() of unsized object")
        return self.shape[0]

    def __bool__(self):
        if self.size != 1:
            raise ValueError(
                "The truth value of an array with more than one element is ambiguous."
            )
        return bool(self._data)

    def __float__(self):
        return float(self._data)

    def __int__(self):
        return int(self._data)

    def __index__(self):
        return int(self._data)

    def __hash__(self):
        return id(self)

    def __repr__(self):
        try:
            return f"{onp.asarray(self._data)!s}\n<NDArray {self.shape} @{self._ctx}>"
        except Exception:  # mxlint: disable=swallowed-exception -- repr must never raise; a traced/aborted array falls back to the shape-only form
            return f"<NDArray {self.shape} {self.dtype} @{self._ctx} (traced)>"

    # ------------------------------------------------------------------
    # host transfer / placement
    # ------------------------------------------------------------------
    def asnumpy(self):
        return onp.asarray(self._data)

    def item(self):
        return self._data.item()

    def tolist(self):
        return onp.asarray(self._data).tolist()

    def asscalar(self):
        if self.size != 1:
            raise ValueError("The current array is not a scalar")
        return self._data.reshape(()).item()

    def astype(self, dtype, copy=True):
        if not copy and onp.dtype(dtype) == self.dtype:
            return self
        return invoke(lambda x: x.astype(dtype), (self,), name="astype")

    def copy(self):
        return invoke(lambda x: x + 0, (self,), name="copy")

    def copyto(self, other):
        """Copy into ``other`` (NDArray → mutate; Context → new array there)."""
        if isinstance(other, NDArray):
            if other is self:
                return other
            data = self._data
            if other._ctx != self._ctx:
                data = jax.device_put(data, other._ctx.jax_device())
            if tuple(other.shape) != self.shape:
                raise ValueError(
                    f"copyto shape mismatch {self.shape} vs {other.shape}"
                )
            if other.dtype != self.dtype:
                data = data.astype(other.dtype)
            other._rebind(data, node=self._node, node_idx=self._node_idx)
            return other
        ctx = Context(other)
        return NDArray(jax.device_put(self._data, ctx.jax_device()), ctx=ctx)

    def as_in_ctx(self, ctx):
        ctx = Context(ctx)
        if ctx == self._ctx:
            return self
        if isinstance(self._data, jax.core.Tracer):
            out = NDArray(self._data, ctx=ctx)
        else:
            out = NDArray(jax.device_put(self._data, ctx.jax_device()), ctx=ctx)
        out._node, out._node_idx = self._node, self._node_idx
        return out

    as_in_context = as_in_ctx
    to_device = as_in_ctx

    def as_np_ndarray(self):
        return self

    def as_nd_ndarray(self):
        return self

    # ------------------------------------------------------------------
    # autograd surface (reference: ndarray.h autograd_entry_, python
    # mxnet/numpy/multiarray.py attach_grad/backward)
    # ------------------------------------------------------------------
    def attach_grad(self, grad_req="write", stype=None):
        """Allocate a gradient buffer; marks this array as a leaf variable
        (reference: `python/mxnet/autograd.py:196` mark_variables).
        ``stype='row_sparse'`` allocates a device-backed RowSparseNDArray
        buffer so wide-embedding grads stay O(touched rows)."""
        if grad_req not in ("write", "add", "null"):
            raise ValueError(f"invalid grad_req {grad_req!r}")
        self._node = None  # leaves are detached from any previous graph
        if stype in (None, "default"):
            self._grad = _GradBuffer(self.shape, self.dtype, self._ctx)
        elif stype == "row_sparse":
            from . import sparse as _sparse
            self._grad = _sparse.zeros("row_sparse", self.shape, self.dtype)
        else:
            raise ValueError(f"unsupported grad stype {stype!r}")
        self._grad_req = grad_req
        return self

    @property
    def grad(self):
        return self._grad

    def zero_grad(self):
        if self._grad is None:
            return
        from .sparse import RowSparseNDArray
        if isinstance(self._grad, RowSparseNDArray):
            self._grad._clear()
        else:
            self._grad._rebind(jnp.zeros(self.shape, self.dtype))

    def detach(self):
        out = NDArray(self._data, ctx=self._ctx)
        return out

    def backward(self, out_grad=None, retain_graph=False, train_mode=True,
                 create_graph=False):
        _iv.backward([self], [out_grad], retain_graph=retain_graph,
                     create_graph=create_graph)

    # ------------------------------------------------------------------
    # indexing
    # ------------------------------------------------------------------
    def _index_data(self, key):
        if isinstance(key, tuple):
            return tuple(k._data if isinstance(k, NDArray) else k for k in key)
        if isinstance(key, NDArray):
            return key._data
        return key

    @staticmethod
    def _bool_mask_ndim(k):
        """A multi-dimensional boolean mask consumes ``k.ndim`` input
        axes under numpy advanced indexing (everything else consumes
        one); 0 for non-boolean keys."""
        dt = getattr(k, "dtype", None)
        try:
            if dt is not None and onp.dtype(dt) == onp.bool_:
                return int(getattr(k, "ndim", 0))
        except TypeError:
            pass  # extension dtypes (PRNG keys, ...) are not bool masks
        return 0

    def _check_index_bounds(self, key):
        """Positional access that RESOLVES past 2^31-1 must fail loudly:
        jax's 32-bit index mode would otherwise OverflowError deep in
        dispatch (gather) or, worse, silently clamp (scatter) — see
        _INT64_INDEX_MSG.  Negative forms resolve against the dim."""
        lim = 2 ** 31 - 1

        def resolve(v, dim):
            v = int(v)
            return v + dim if (v < 0 and dim is not None) else v

        keys = key if isinstance(key, tuple) else (key,)
        # map key elements to axes the way numpy does: None (newaxis)
        # consumes no input axis, Ellipsis consumes the unmatched middle,
        # and an n-dim BOOLEAN mask consumes n axes (ADVICE r5: counting
        # it as one made later negative ints resolve against the wrong
        # dim)
        n_explicit = sum(NDArray._bool_mask_ndim(k) or 1 for k in keys
                         if k is not None and k is not Ellipsis)
        axis = 0
        dims = []
        for k in keys:
            if k is None:
                dims.append(None)
            elif k is Ellipsis:
                dims.append(None)
                axis += max(len(self.shape) - n_explicit, 0)
            else:
                bn = NDArray._bool_mask_ndim(k)
                if bn:
                    # mask positions are within-bounds by construction;
                    # the cursor just advances past the axes it consumes
                    dims.append(None)
                    axis += bn
                else:
                    dims.append(self.shape[axis]
                                if axis < len(self.shape) else None)
                    axis += 1
        for k, dim in zip(keys, dims):
            if k is None or k is Ellipsis:
                continue
            if isinstance(k, (int, onp.integer)):
                if resolve(k, dim) > lim:
                    raise IndexError(_INT64_INDEX_MSG)
            elif isinstance(k, slice):
                # the slice START becomes a 32-bit dynamic_slice operand;
                # a large STOP with a small start only sets the (64-bit
                # static) size, so a[:huge] stays legal
                if k.start is not None and resolve(k.start, dim) > lim:
                    raise IndexError(_INT64_INDEX_MSG)

    def __getitem__(self, key):
        self._check_index_bounds(key)
        k = self._index_data(key)
        try:
            return invoke(lambda x: x[k], (self,), name="getitem")
        except OverflowError:
            raise IndexError(_INT64_INDEX_MSG) from None

    @staticmethod
    def _plan_slice_update(shape, key):
        """Classify ``key`` as a write expressible WITHOUT a scatter —
        ints and step-1 slices only — returning ``(starts, blk_shape,
        idx_shape)`` for a scatter-free slice+concat lowering
        (``blk_shape`` keeps int axes as size-1; ``idx_shape`` drops
        them, numpy's value-broadcast shape), or None when the key needs
        gather/scatter position operands (arrays, bool masks, strides,
        newaxis) or an offset past 2^31-1.  Lets full-slice / contiguous
        assignments work on >2^31-element arrays, where jax's 32-bit
        scatter indices silently drop the write (ADVICE r5)."""
        lim = 2 ** 31 - 1
        keys = list(key) if isinstance(key, tuple) else [key]
        if any(k is Ellipsis for k in keys):
            if sum(1 for k in keys if k is Ellipsis) > 1:
                return None
            i = keys.index(Ellipsis)
            keys[i:i + 1] = [slice(None)] * (len(shape) - (len(keys) - 1))
        if len(keys) > len(shape):
            return None
        keys += [slice(None)] * (len(shape) - len(keys))
        starts, blk, idx = [], [], []
        for k, dim in zip(keys, shape):
            if isinstance(k, bool):
                return None
            if isinstance(k, (int, onp.integer)):
                v = int(k) + (dim if k < 0 else 0)
                if not 0 <= v < dim or v > lim:
                    return None
                starts.append(v)
                blk.append(1)
            elif isinstance(k, slice):
                if k.step not in (None, 1):
                    return None
                try:
                    lo, hi, _ = k.indices(dim)
                except TypeError:
                    return None
                if lo > lim:
                    return None
                starts.append(lo)
                n = max(hi - lo, 0)
                blk.append(n)
                idx.append(n)
            else:
                return None  # arrays / masks / newaxis: real scatter
        return tuple(starts), tuple(blk), tuple(idx)

    def __setitem__(self, key, value):
        # scatter on a >2^31-element array silently NO-OPS in 32-bit
        # index mode (jax truncates the index dtype and the write is
        # dropped, at any position — probed in tests/test_large_tensor.py)
        # ... but full-slice / contiguous-slice assignments don't need a
        # scatter at all: they lower to broadcast + static-slice/concat
        # embedding (64-bit-safe static bounds, sub-2^31 starts).  Only
        # writes that genuinely carry gather/scatter position operands
        # keep the fence.
        if self.size > _SETITEM_SCATTER_LIMIT:
            plan = self._plan_slice_update(self.shape, key)
            if plan is None:
                raise IndexError(_INT64_INDEX_MSG)
            starts, blk_shape, idx_shape = plan

            def embed(x, u, sts, blk):
                # STATIC slice + concat along each partial axis, value
                # broadcast at the leaf: every op here is probed safe on
                # >2^31-element operands, whereas dynamic_update_slice
                # (the obvious lowering) segfaulted on them when this
                # was written (XLA CPU) — hence this shape
                for ax, (lo, n) in enumerate(zip(sts, blk)):
                    if lo == 0 and n == x.shape[ax]:
                        continue
                    pre = jax.lax.slice_in_dim(x, 0, lo, axis=ax)
                    mid = jax.lax.slice_in_dim(x, lo, lo + n, axis=ax)
                    post = jax.lax.slice_in_dim(x, lo + n, x.shape[ax],
                                                axis=ax)
                    mid = embed(mid, u, sts[:ax] + (0,) + sts[ax + 1:],
                                blk)
                    return jnp.concatenate([pre, mid, post], axis=ax)
                return jnp.broadcast_to(u, x.shape).astype(x.dtype)

            def place(x, v):
                v = v.astype(x.dtype)
                try:
                    u = jnp.broadcast_to(v, idx_shape).reshape(blk_shape)
                except (ValueError, TypeError):
                    u = jnp.broadcast_to(v, blk_shape)
                return embed(x, u, starts, blk_shape)

            if isinstance(value, NDArray):
                self._rebind(invoke(place, (self, value), name="setitem"))
            else:
                self._rebind(invoke(
                    lambda x: place(x, jnp.asarray(value)), (self,),
                    name="setitem"))
            return
        self._check_index_bounds(key)
        k = self._index_data(key)
        try:
            if isinstance(value, NDArray):
                def setter(x, v):
                    return x.at[k].set(v.astype(x.dtype))
                self._rebind(invoke(setter, (self, value), name="setitem"))
            else:
                def setter(x):
                    return x.at[k].set(value)
                self._rebind(invoke(setter, (self,), name="setitem"))
        except OverflowError:
            raise IndexError(_INT64_INDEX_MSG) from None

    # ------------------------------------------------------------------
    # shape ops (delegate to jnp through the dispatcher)
    # ------------------------------------------------------------------
    def reshape(self, *shape, **kwargs):
        if len(shape) == 1 and isinstance(shape[0], (tuple, list)):
            shape = tuple(shape[0])
        # reference allows 0 = copy-dim, -1 = infer (ndarray.cc reshape)
        shape = tuple(
            self.shape[i] if s == 0 else s for i, s in enumerate(shape)
        ) if 0 in shape else shape
        return invoke(lambda x: jnp.reshape(x, shape), (self,), name="reshape")

    def transpose(self, *axes):
        if len(axes) == 1 and isinstance(axes[0], (tuple, list)):
            axes = tuple(axes[0])
        axes = axes if axes else None
        return invoke(lambda x: jnp.transpose(x, axes), (self,), name="transpose")

    def flatten(self):
        return self.reshape(-1)

    def squeeze(self, axis=None):
        return invoke(lambda x: jnp.squeeze(x, axis), (self,), name="squeeze")

    def expand_dims(self, axis):
        return invoke(lambda x: jnp.expand_dims(x, axis), (self,), name="expand_dims")

    def swapaxes(self, a1, a2):
        return invoke(lambda x: jnp.swapaxes(x, a1, a2), (self,), name="swapaxes")

    def broadcast_to(self, shape):
        return invoke(lambda x: jnp.broadcast_to(x, shape), (self,), name="broadcast_to")

    def repeat(self, repeats, axis=None):
        return invoke(lambda x: jnp.repeat(x, repeats, axis), (self,), name="repeat")

    def clip(self, a_min=None, a_max=None):
        return invoke(lambda x: jnp.clip(x, a_min, a_max), (self,), name="clip")

    def abs(self):
        return invoke(jnp.abs, (self,), name="abs")

    def _maybe_out(self, res, out):
        # numpy-compatible ``out=``: the reference's generated method
        # signatures accept it (`python/mxnet/numpy/multiarray.py` reduce
        # methods); on XLA it is a rebind of the destination wrapper.
        # Shape must match (numpy raises too); the value is cast to the
        # destination's dtype so holders of `out` keep its contract.
        if out is None:
            return res
        if tuple(out.shape) != tuple(res.shape):
            raise ValueError(
                f"out= has shape {tuple(out.shape)}, result is "
                f"{tuple(res.shape)}")
        if out.dtype != res.dtype:
            res = res.astype(out.dtype)
        return out._rebind(res)

    def sum(self, axis=None, dtype=None, out=None, keepdims=False):
        return self._maybe_out(
            invoke(lambda x: jnp.sum(x, axis=axis, dtype=dtype, keepdims=keepdims),
                   (self,), name="sum"), out)

    def mean(self, axis=None, dtype=None, out=None, keepdims=False):
        return self._maybe_out(
            invoke(lambda x: jnp.mean(x, axis=axis, dtype=dtype, keepdims=keepdims),
                   (self,), name="mean"), out)

    def std(self, axis=None, dtype=None, out=None, ddof=0, keepdims=False):
        return self._maybe_out(
            invoke(lambda x: jnp.std(x, axis=axis, dtype=dtype, ddof=ddof,
                                     keepdims=keepdims),
                   (self,), name="std"), out)

    def var(self, axis=None, dtype=None, out=None, ddof=0, keepdims=False):
        return self._maybe_out(
            invoke(lambda x: jnp.var(x, axis=axis, dtype=dtype, ddof=ddof,
                                     keepdims=keepdims),
                   (self,), name="var"), out)

    def cumsum(self, axis=None, dtype=None, out=None):
        return self._maybe_out(
            invoke(lambda x: jnp.cumsum(x, axis=axis, dtype=dtype),
                   (self,), name="cumsum"), out)

    def round(self, decimals=0, out=None):
        return self._maybe_out(
            invoke(lambda x: jnp.round(x, decimals), (self,), name="round",
                   differentiable=False), out)

    def take(self, indices, axis=None, mode="clip", out=None):
        return self._maybe_out(
            invoke(lambda x, i: jnp.take(x, i, axis=axis, mode=mode),
                   (self, indices), name="take"), out)

    def prod(self, axis=None, dtype=None, out=None, keepdims=False):
        return self._maybe_out(
            invoke(lambda x: jnp.prod(x, axis=axis, dtype=dtype, keepdims=keepdims),
                   (self,), name="prod"), out)

    def max(self, axis=None, out=None, keepdims=False):
        return self._maybe_out(
            invoke(lambda x: jnp.max(x, axis=axis, keepdims=keepdims),
                   (self,), name="max"), out)

    def min(self, axis=None, out=None, keepdims=False):
        return self._maybe_out(
            invoke(lambda x: jnp.min(x, axis=axis, keepdims=keepdims),
                   (self,), name="min"), out)

    def all(self, axis=None, out=None, keepdims=False):
        return self._maybe_out(
            invoke(lambda x: jnp.all(x, axis=axis, keepdims=keepdims),
                   (self,), name="all", differentiable=False), out)

    def any(self, axis=None, out=None, keepdims=False):
        return self._maybe_out(
            invoke(lambda x: jnp.any(x, axis=axis, keepdims=keepdims),
                   (self,), name="any", differentiable=False), out)

    def argmax(self, axis=None, out=None):
        return self._maybe_out(
            invoke(lambda x: jnp.argmax(x, axis=axis), (self,),
                   name="argmax", differentiable=False), out)

    def argmin(self, axis=None, out=None):
        return self._maybe_out(
            invoke(lambda x: jnp.argmin(x, axis=axis), (self,),
                   name="argmin", differentiable=False), out)

    def dot(self, other):
        return invoke(jnp.dot, (self, other), name="dot")

    def norm(self, ord=None, axis=None, keepdims=False):
        return invoke(lambda x: jnp.linalg.norm(x, ord=ord, axis=axis, keepdims=keepdims),
                      (self,), name="norm")

    def tostype(self, stype):
        """Convert storage type (reference `cast_storage`): 'csr' /
        'row_sparse' produce the host-side containers in `mx.nd.sparse`
        (XLA has no sparse buffers; compute stays dense on TPU)."""
        if stype == "default":
            return self
        from . import sparse as _sparse
        return _sparse.array(self, stype=stype)

    @property
    def stype(self):
        return "default"

    # ------------------------------------------------------------------
    # arithmetic operators
    # ------------------------------------------------------------------
    def _binary(self, other, fun, name, reflect=False):
        if isinstance(other, NDArray) or isinstance(other, numeric_types) or (
            isinstance(other, (onp.ndarray, jax.Array))
        ):
            a, b = (other, self) if reflect else (self, other)
            return invoke(fun, (a, b), name=name)
        return NotImplemented

    def __add__(self, other):
        return self._binary(other, jnp.add, "add")

    def __radd__(self, other):
        return self._binary(other, jnp.add, "add", reflect=True)

    def __sub__(self, other):
        return self._binary(other, jnp.subtract, "subtract")

    def __rsub__(self, other):
        return self._binary(other, jnp.subtract, "subtract", reflect=True)

    def __mul__(self, other):
        return self._binary(other, jnp.multiply, "multiply")

    def __rmul__(self, other):
        return self._binary(other, jnp.multiply, "multiply", reflect=True)

    def __truediv__(self, other):
        return self._binary(other, jnp.true_divide, "true_divide")

    def __rtruediv__(self, other):
        return self._binary(other, jnp.true_divide, "true_divide", reflect=True)

    def __floordiv__(self, other):
        return self._binary(other, jnp.floor_divide, "floor_divide")

    def __rfloordiv__(self, other):
        return self._binary(other, jnp.floor_divide, "floor_divide", reflect=True)

    def __mod__(self, other):
        return self._binary(other, jnp.mod, "mod")

    def __rmod__(self, other):
        return self._binary(other, jnp.mod, "mod", reflect=True)

    def __pow__(self, other):
        return self._binary(other, jnp.power, "power")

    def __rpow__(self, other):
        return self._binary(other, jnp.power, "power", reflect=True)

    def __matmul__(self, other):
        return self._binary(other, jnp.matmul, "matmul")

    def __rmatmul__(self, other):
        return self._binary(other, jnp.matmul, "matmul", reflect=True)

    def __neg__(self):
        return invoke(jnp.negative, (self,), name="negative")

    def __pos__(self):
        return self

    def __abs__(self):
        return invoke(jnp.abs, (self,), name="abs")

    def __invert__(self):
        return invoke(jnp.invert, (self,), name="invert", differentiable=False)

    # in-place: re-bind (tape-safe, see module docstring)
    def __iadd__(self, other):
        return self._rebind(self._binary(other, jnp.add, "add"))

    def __isub__(self, other):
        return self._rebind(self._binary(other, jnp.subtract, "subtract"))

    def __imul__(self, other):
        return self._rebind(self._binary(other, jnp.multiply, "multiply"))

    def __itruediv__(self, other):
        return self._rebind(self._binary(other, jnp.true_divide, "true_divide"))

    def __imod__(self, other):
        return self._rebind(self._binary(other, jnp.mod, "mod"))

    def __ipow__(self, other):
        return self._rebind(self._binary(other, jnp.power, "power"))

    # comparisons (non-differentiable)
    def _compare(self, other, fun, name):
        return invoke(fun, (self, other), name=name, differentiable=False)

    def __eq__(self, other):
        if other is None:
            return False
        return self._compare(other, jnp.equal, "equal")

    def __ne__(self, other):
        if other is None:
            return True
        return self._compare(other, jnp.not_equal, "not_equal")

    def __lt__(self, other):
        return self._compare(other, jnp.less, "less")

    def __le__(self, other):
        return self._compare(other, jnp.less_equal, "less_equal")

    def __gt__(self, other):
        return self._compare(other, jnp.greater, "greater")

    def __ge__(self, other):
        return self._compare(other, jnp.greater_equal, "greater_equal")

    # numpy interop
    def __array__(self, dtype=None):
        arr = onp.asarray(self._data)
        return arr.astype(dtype) if dtype is not None else arr

    def __iter__(self):
        for i in range(len(self)):
            yield self[i]

    def __dlpack__(self, *a, **kw):
        return self._data.__dlpack__(*a, **kw)


_iv.set_ndarray_class(NDArray)


class _GradBuffer(NDArray):
    """The gradient buffer `NDArray.attach_grad` gives: zeros, made when they
    are first read.  A backward pass with ``grad_req='write'`` only rebinds
    it, and `gluon.FusedTrainStep`, whose gradients live inside its compiled
    step, never touches it, so until something reads it the buffer holds no
    device memory."""

    def __init__(self, shape, dtype, ctx):
        self._zeros = (tuple(shape), dtype)
        self._buf = None
        self._ctx = ctx
        self._grad = None
        self._grad_req = "null"
        self._node = None
        self._node_idx = 0
        self._version = 0

    @property
    def _data(self):
        if self._buf is None:
            self._buf = jnp.zeros(*self._zeros)
        return self._buf

    @_data.setter
    def _data(self, value):
        self._buf = value

    @property
    def shape(self):
        return self._zeros[0] if self._buf is None else tuple(self._buf.shape)

    @property
    def dtype(self):
        return onp.dtype(self._zeros[1] if self._buf is None else
                         self._buf.dtype)


# ---------------------------------------------------------------------------
# creation helpers (reference: mx.nd.array / ndarray.cc)
# ---------------------------------------------------------------------------
def array(source, ctx=None, dtype=None, device=None):
    ctx = ctx or device
    return NDArray(source if not isinstance(source, NDArray) else source._data,
                   ctx=ctx, dtype=dtype)


def empty(shape, ctx=None, dtype=None, device=None):
    ctx = ctx or device
    return NDArray(jnp.zeros(shape, dtype or onp.float32), ctx=ctx)


def from_jax(x, ctx=None):
    return NDArray(x, ctx=ctx)


def waitall():
    """Drain all pending device work (reference `mx.nd.waitall`,
    `python/mxnet/ndarray/ndarray.py:231`).

    jax has no wait-for-everything call, so this is an ordered drain: a
    device runs the programs of one process in the order they were
    enqueued, and a scalar program enqueued now on each local device is
    ready only after all that was queued before it.  `chip_smoke.py` holds
    this to the TPU backend on every run (a step timed to
    ``block_until_ready`` of its outputs and to ``waitall()`` must agree).
    A deferred execution error (OOM, kernel failure) surfaces here, as the
    reference rethrows at WaitForAll.
    """
    for d in jax.local_devices():
        with jax.default_device(d):
            (jnp.zeros((), onp.float32) + 0).block_until_ready()
