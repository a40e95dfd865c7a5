"""One-dispatch training step.

Reference analogue: engine op-bulking (`src/engine/threaded_engine.h:507`)
plus CachedOp static_alloc (`src/imperative/cached_op.h:413`) — MXNet's
answer to per-op dispatch overhead.  On TPU the equivalent leverage is far
larger: ``FusedTrainStep`` compiles loss forward, all gradients, and the
optimizer update into a SINGLE donated XLA program, so a training step is
one host→device dispatch regardless of model size.  This is the
documented fast path; the eager record/backward/step triple remains fully
supported and numerically identical.
"""
from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp
import numpy as onp

from .. import random as _rng
from .. import telemetry as _telemetry
from ..resilience import faultline as _faultline
from ..resilience.policies import step_skip_counter as _step_skip_counter
from ..ndarray.ndarray import NDArray
from .block import _TREEDEFS, _intern_treedef, _is_nd, _scoped_forward

__all__ = ["FusedTrainStep"]


def _as_tuple(x):
    return x if isinstance(x, tuple) else (x,)


class FusedTrainStep:
    """Fuse ``loss = block(*inputs); loss.backward(); trainer.step(bs)``
    into one jitted program.

    ``block`` must produce the loss (its first output leaf is summed as the
    backward seed, matching ``backward()``'s ones-cotangent), and the
    trainer's optimizer must expose ``update_math`` (all built-ins do).

    >>> step = FusedTrainStep(mod, trainer)
    >>> loss = step(x, y, batch_size=128)

    **SPMD**: pass ``mesh`` (a `jax.sharding.Mesh`, e.g. from
    `parallel.make_mesh`) to run the same single program across every chip
    of the mesh — parameters are placed by ``partition_rules`` (regex →
    PartitionSpec, Megatron-style; unmatched params replicate), inputs are
    sharded by ``data_spec`` (default: batch over the mesh's first axis),
    and XLA inserts the gradient collectives over ICI.  This is the
    `kvstore='tpu_ici'` training path with zero per-step python overhead:

    >>> mesh = parallel.make_mesh({"dp": -1})
    >>> step = FusedTrainStep(mod, trainer, mesh=mesh)

    **Recipes**: pass ``recipe`` (a `parallel.ShardingRecipe` or its
    config string, e.g. ``"dp2.tp2"``) and the whole SPMD setup derives
    from it — the mesh is built (unless an explicit ``mesh`` narrows the
    device set), the partition rules are collected from every block's
    ``partition_rules()`` over the tree (with ``partition_rules=``
    overrides checked first), the input spec comes from the recipe's data
    axes, and placement runs the strict coverage audit under tp/pp
    recipes.  With neither ``mesh`` nor ``recipe``, the
    ``MXNET_PARALLEL_RECIPE`` environment default applies (unset: the
    single-device step).

    >>> step = FusedTrainStep(mod, trainer, recipe="dp2.tp2")
    """

    def __init__(self, block, trainer, mesh=None, partition_rules=None,
                 data_spec=None, scaler=None, recipe=None):
        self._block = block
        self._trainer = trainer
        # loss scaler (amp): scales the backward seed in-program, and the
        # step-guard verdict ticks its window.  `amp.init_trainer` attaches
        # one to the trainer; an explicit `scaler=` overrides.
        self._scaler = scaler if scaler is not None else \
            getattr(trainer, "_amp_loss_scaler", None)
        # finite-grad verdict of the last dispatched step (device scalar;
        # reading it as bool() syncs).  None until the first step.
        self.last_step_finite = None
        if recipe is None and mesh is None:
            from .. import env as _env
            recipe = _env.parallel_recipe()
        self._recipe = None
        if recipe is not None:
            from ..parallel.recipe import ShardingRecipe
            self._recipe = ShardingRecipe(recipe)
            if mesh is None:
                mesh = self._recipe.build_mesh()
            if data_spec is None:
                data_spec = self._recipe.data_spec()
        self._mesh = mesh
        self._rules = partition_rules or []
        if mesh is not None and data_spec is None:
            from jax.sharding import PartitionSpec
            data_spec = PartitionSpec(mesh.axis_names[0])
        self._data_spec = data_spec
        self._jit = None
        self._plist = None
        self._train_idx = None
        self._opt_index = None
        self._n_steps = 0       # steps dispatched; the spans of one share it

    def _setup(self, args):
        block, trainer = self._block, self._trainer
        from ..optimizer.optimizer import Optimizer as _OptBase
        opt = trainer._optimizer
        if getattr(opt, "supports_fused", True) is False or \
                type(opt).update_math is _OptBase.update_math:
            raise ValueError(
                f"{type(opt).__name__} has no update_math; "
                "use the eager record/backward/step path")
        block._ensure_shapes(*args)   # deferred shapes before state alloc
        trainer._init_kvstore()
        trainer._init_states()
        params = block.collect_params()
        self._plist = [params[k] for k in sorted(params)]
        for p in self._plist:
            if len(p.list_ctx()) != 1:
                raise ValueError(
                    "FusedTrainStep is single-device; use kvstore DP or the "
                    "SPMD mesh path for multi-device")
        # trainable = has a gradient AND is managed by this trainer; params
        # outside the trainer (frozen fine-tuning subsets) stay constant,
        # matching the eager path where the trainer only updates its own
        by_id = {id(p): i for i, p in enumerate(trainer._params)}
        self._train_idx = tuple(
            k for k, p in enumerate(self._plist)
            if p.grad_req != "null" and id(p) in by_id)
        self._opt_index = tuple(by_id[id(self._plist[k])]
                                for k in self._train_idx)
        if self._mesh is not None:
            self._place_on_mesh(params)

    def _place_on_mesh(self, params):
        """Shard parameters/optimizer state onto the mesh by the partition
        rules via `parallel.shard_parameters`; XLA then derives every
        collective."""
        from jax.sharding import NamedSharding, PartitionSpec

        from ..parallel.mesh import axes_size, global_put, shard_parameters

        self._global_put = global_put
        mesh, trainer = self._mesh, self._trainer
        if self._recipe is not None:
            # explicit partition_rules act as overrides: checked before
            # the block tree's collected rules (first match wins)
            rules = self._recipe.collect_rules(self._block,
                                               overrides=self._rules)
            strict = self._recipe.strict()
        else:
            rules, strict = self._rules, False
        specs = shard_parameters(params, mesh, rules, strict=strict)
        names = sorted(params)
        rep = NamedSharding(mesh, PartitionSpec())
        self._rep = rep
        # per-rank input shardings: the spec is truncated to the array's
        # rank so a rank-2 data_spec still places rank-1 labels
        self._data_shardings = [
            NamedSharding(mesh, PartitionSpec(*self._data_spec[:r]))
            for r in range(1, 9)]
        # mesh axes of the LEADING dim only: the divisibility checks, and
        # what kernels traced under the step's mesh_scope shard over
        lead = self._data_spec[0] if len(self._data_spec) else None
        self._batch_axes = (lead,) if isinstance(lead, str) else \
            tuple(lead or ())
        self._dp_size = axes_size(mesh, self._batch_axes)
        self._shardings = [NamedSharding(mesh, specs[n]) for n in names]
        for i, k in zip(self._opt_index, self._train_idx):
            p_shape = self._plist[k].shape
            for s_nd in _as_tuple(trainer._states[i]):
                sh = self._shardings[k] if s_nd.shape == p_shape else rep
                s_nd._rebind(global_put(s_nd._data, sh))

    def _build(self, treedef_id):
        block = self._block
        optimizer = self._trainer._optimizer
        plist = self._plist
        train_idx = self._train_idx
        holder = []
        self._aux_holder = holder

        n_opt = len(self._opt_index)
        idx_by_param = {id(p): k for k, p in enumerate(plist)}
        tpos = {k: j for j, k in enumerate(train_idx)}
        if self._mesh is not None:
            from ..parallel.mesh import mesh_scope
            step_mesh = mesh_scope(self._mesh, self._batch_axes)
        else:
            step_mesh = contextlib.nullcontext()

        def fused(train_ws, const_pd, states, root_key, flat_inputs, scal,
                  counter, clip, treedef_id):
            if root_key.dtype == jnp.uint32:  # multi-process: raw key data
                root_key = jax.random.wrap_key_data(root_key)
            # per-step scalars arrive as ONE bundled f32 array (one H2D
            # put instead of 4-6 tiny ones):
            # [lrs(n), wds(n), ts(n), rescale].  The PRNG
            # stream counter ships as its OWN 1-element int32 array
            # (ADVICE r5): the old int32-bits-viewed-as-f32 trick put
            # counters >= 0x7F800000 on inf/NaN bitpatterns, which any
            # canonicalizing transfer/compiler pass may silently rewrite
            # — a float bundle is not a lossless int channel.  The key
            # still folds IN-PROGRAM, so the per-step dispatch saving
            # stands, and the key is identical to host-side new_key().
            # [lrs(n), wds(n), ts(n), rescale, loss_scale]: loss_scale
            # multiplies the backward seed (amp f16 — small grads survive
            # the wire), rescale already divides it back out.
            lrs = scal[:n_opt]
            wds = scal[n_opt:2 * n_opt]
            ts = scal[2 * n_opt:3 * n_opt]
            rescale = scal[3 * n_opt]
            loss_scale = scal[3 * n_opt + 1]
            key = jax.random.fold_in(root_key, counter[0])

            def loss_fn(tws):
                full = list(const_pd)
                for j, k in enumerate(train_idx):
                    full[k] = tws[j]
                out_datas, aux = _scoped_forward(
                    block, plist, full, key, flat_inputs,
                    _TREEDEFS[treedef_id], True, backward=True)
                holder.clear()
                holder.extend(getattr(a, "_param_ref", None)
                              for a, _v in aux.updates)
                aux_datas = [v._data if _is_nd(v) else v
                             for _a, v in aux.updates]
                first = jax.tree_util.tree_leaves(out_datas)[0]
                return jnp.sum(first.astype(jnp.float32)) * loss_scale, \
                    (out_datas, aux_datas)

            # the scope tells Pallas kernels in the model that this trace
            # is partitioned over a mesh (they cannot be auto-partitioned)
            with step_mesh:
                (_lsum, (outs, auxs)), grads = jax.value_and_grad(
                    loss_fn, has_aux=True)(train_ws)
            # old values for aux updates (BN running stats), so the
            # step-guard can hold them too: holder was filled at trace
            # time by loss_fn, and aux params live in const_pd (or, for
            # the odd trainable one, in train_ws)
            aux_old = []
            for pref in holder:
                k = idx_by_param.get(id(pref)) if pref is not None else None
                if k is None:
                    aux_old.append(None)
                else:
                    aux_old.append(train_ws[tpos[k]] if k in tpos
                                   else const_pd[k])
            # the optimizer is a census row of its own: scope the update
            # math so its HLO cost never pollutes a layer's bucket
            with jax.named_scope("optimizer"):
                # finite-grad step-guard: one verdict over ALL rescaled
                # grads, computed BEFORE clipping (clip would launder an
                # inf into a finite value and hide the overflow).  Pure
                # elementwise+reduce — adds no collective, so hloscan's
                # launch-count pin is untouched.  A non-finite step keeps
                # weights, optimizer state, and aux stats bitwise intact.
                gs = []
                finite = jnp.bool_(True)
                for j in range(len(train_idx)):
                    g = grads[j].astype(jnp.float32) * rescale
                    finite = jnp.logical_and(
                        finite, jnp.all(jnp.isfinite(g)))
                    if clip is not None:
                        g = jnp.clip(g, -clip, clip)
                    gs.append(g)
                new_ws, new_states = [], []
                for j in range(len(train_idx)):
                    w = train_ws[j]
                    g = gs[j].astype(w.dtype)
                    nw, nst = optimizer.update_math(
                        w, g, states[j], lrs[j], wds[j], ts[j])
                    nw = jnp.where(finite, nw, w)
                    nst = tuple(jnp.where(finite, sn, so)
                                for sn, so in zip(_as_tuple(nst),
                                                  states[j]))
                    new_ws.append(nw)
                    new_states.append(nst)
                auxs = [jnp.where(finite, v, old) if old is not None else v
                        for v, old in zip(auxs, aux_old)]
            return outs, auxs, tuple(new_ws), tuple(new_states), finite

        return jax.jit(fused, donate_argnums=(0, 2),
                       static_argnums=(7, 8))

    def __call__(self, *args, batch_size=1):
        return self.step(*args, batch_size=batch_size)

    def _prepare(self, args, batch_size):
        """Everything between user args and the jitted call: setup on
        first use, per-step scalar bundling, mesh placement, treedef
        interning.  Returns the exact argument tuple ``self._jit`` is
        invoked with — shared by :meth:`step` and the AOT capture
        methods (:meth:`trace` / :meth:`lower`), so what hloscan
        inspects is the very program the step dispatches."""
        with _telemetry.span("fused_step.prepare"):
            return self._call_args(args, batch_size)

    def _call_args(self, args, batch_size):
        flat, treedef = jax.tree_util.tree_flatten(args, is_leaf=_is_nd)
        treedef_id = _intern_treedef(treedef)
        if self._jit is None:
            # what the first call does once: set-up's part of it, under the
            # first `fused_step.prepare` on the span record
            with _telemetry.span("fused_step.build", cat="setup") as built:
                self._setup(args)
                self._jit = self._build(treedef_id)
                built.args.update(
                    params=len(self._plist),
                    states=sum(len(_as_tuple(self._trainer._states[i]))
                               for i in self._opt_index))
        trainer = self._trainer
        optimizer = trainer._optimizer
        optimizer.rescale_grad = trainer._scale / batch_size
        plist = self._plist

        flat = [a._data if _is_nd(a) else a for a in flat]
        if self._mesh is not None:
            # batch-shard inputs whose leading dim divides over the data
            # axis (batch tensors); broadcastable extras — masks with a
            # size-1 batch dim, per-feature vectors — replicate instead.
            # params/states already live on the mesh, so the jitted
            # program computes SPMD and XLA inserts the gradient psum.
            def place(d):
                if not hasattr(d, "ndim") or d.ndim == 0:
                    return d
                if d.shape[0] >= self._dp_size and \
                        d.shape[0] % self._dp_size == 0:
                    target = self._data_shardings[min(d.ndim, 8) - 1]
                else:
                    target = self._rep
                # the sharded feed path (parallel.shard_put via
                # DevicePrefetcher/DataLoader) delivers global arrays
                # already laid out per-device — re-placing them would
                # re-replicate through the host, so equivalently-sharded
                # inputs pass through untouched
                cur = getattr(d, "sharding", None)
                if cur is not None and cur.is_equivalent_to(target, d.ndim):
                    return d
                return self._global_put(d, target)
            flat = [place(d) for d in flat]

        pd = [p.data()._data for p in plist]
        train_ws = tuple(pd[k] for k in self._train_idx)
        const_pd = tuple(
            d if k not in set(self._train_idx) else None
            for k, d in enumerate(pd))
        states = tuple(
            tuple(s._data for s in _as_tuple(trainer._states[i]))
            for i in self._opt_index)

        n_opt = len(self._opt_index)
        scal = onp.empty(3 * n_opt + 2, onp.float32)
        for j, i in enumerate(self._opt_index):
            optimizer._update_count(i)
            scal[j] = optimizer._get_lr(i)
            scal[n_opt + j] = optimizer._get_wd(i)
            scal[2 * n_opt + j] = optimizer._index_update_count[i]
        # amp: the backward seed is multiplied by loss_scale in-program;
        # fold 1/loss_scale into rescale so the update sees true grads
        loss_scale = float(self._scaler.loss_scale) \
            if self._scaler is not None else 1.0
        rescale = optimizer.rescale_grad / loss_scale
        inject = _faultline.poll("train.grads")
        if inject == "nan_grad":
            # poison the rescale factor: every gradient goes NaN and the
            # in-program step-guard must hold the update
            rescale = float("nan")
        elif inject is not None:
            _faultline.raise_fault("train.grads", inject)
        scal[3 * n_opt] = rescale
        scal[3 * n_opt + 1] = loss_scale
        root, counter = _rng.root_and_counter()
        # separate int32 channel — never routed through float bits (the
        # NaN-canonicalization hazard; see _build)
        cnt = onp.asarray([counter], onp.int32)
        if self._mesh is not None and not self._rep.is_fully_addressable:
            # multi-process mesh: every per-step input must be a global
            # array (identical on all processes — deterministic streams).
            # The root key transfers once per seed, not per step.
            gp = self._global_put
            scal = gp(scal, self._rep)
            cnt = gp(cnt, self._rep)
            # cache keyed by a STRONG reference to the root object: an
            # id()-only check could spuriously hit after a reseed if the
            # old key object's address were reused
            if getattr(self, "_root_obj", None) is not root:
                self._root_global = gp(
                    onp.asarray(jax.random.key_data(root)), self._rep)
                self._root_obj = root
            root = self._root_global
        else:
            scal = jnp.asarray(scal)
            cnt = jnp.asarray(cnt)
        return (train_ws, const_pd, states, root, flat, scal, cnt,
                optimizer.clip_gradient, treedef_id)

    def step(self, *args, batch_size=1):
        self._n_steps += 1
        # the whole call is one span, and the `fused-step` phase histogram
        # observes it; prepare and launch are its children, and what is
        # left of it is the rebinding
        with _telemetry.step_phase("fused-step", name="fused_step.step",
                                   step=self._n_steps):
            call_args = self._prepare(args, batch_size)
            _telemetry.mark_step()
            with _telemetry.span("fused_step.launch") as launch:
                outs, auxs, new_ws, new_states, finite = \
                    self._jit(*call_args)
                # the watchdog's own reading of the jit's cache: true on
                # the first compile too, which it does not count a retrace
                launch.args["compiled"] = _telemetry.watchdog().observe(
                    self._jit,
                    name=f"FusedTrainStep[{type(self._block).__name__}]",
                    scope_root=self._block.name) > 0
            # the donated inputs' last references: dropped here they die
            # as `_rebind` re-points their NDArrays, inside the step's
            # span, and not as this frame unwinds, inside nobody's
            del call_args
            return self._rebind(outs, auxs, new_ws, new_states, finite)

    def _rebind(self, outs, auxs, new_ws, new_states, finite):
        """Point every parameter, state and auxiliary at what the step
        returned, and wrap the outputs."""
        trainer, plist = self._trainer, self._plist
        for j, k in enumerate(self._train_idx):
            plist[k].data()._rebind(new_ws[j])
        for i, nst in zip(self._opt_index, new_states):
            for s_nd, s_new in zip(_as_tuple(trainer._states[i]),
                                   _as_tuple(nst)):
                s_nd._rebind(s_new)
        for p, v in zip(self._aux_holder, auxs):
            if p is not None:
                p.data()._rebind(v)

        # the guard verdict stays on device (no sync) unless a scaler is
        # attached — then one scalar pull per step drives the scale
        # trajectory and the skip telemetry
        self.last_step_finite = finite
        scaler = self._scaler
        if scaler is not None:
            ok = bool(finite)
            if not ok:
                _step_skip_counter().inc()
                _faultline.recovered("train.grads", "nan_grad")
            scaler.update_scale(not ok)

        ctx = plist[0].list_ctx()[0] if plist else None
        return jax.tree_util.tree_map(
            lambda o: NDArray(o, ctx=ctx), outs)

    # -- AOT capture (mxnet_tpu.analysis / tools.hloscan) ----------------
    # Same argument prep as step(), so the traced/lowered program is the
    # one a real step dispatches — not a reconstruction.  Neither method
    # executes the step: weights and optimizer state are untouched (the
    # per-step scalar bookkeeping in _prepare does advance update counts,
    # as a dry trace of one step should).

    def trace(self, *args, batch_size=1):
        """``jax.stages.Traced`` for one step (``.jaxpr`` for analysis)."""
        call_args = self._prepare(args, batch_size)  # builds self._jit
        return self._jit.trace(*call_args)

    def lower(self, *args, batch_size=1):
        """``jax.stages.Lowered`` for one step — ``.compiler_ir()`` /
        ``.compile().as_text()`` give hloscan its input texts."""
        call_args = self._prepare(args, batch_size)  # builds self._jit
        return self._jit.lower(*call_args)
