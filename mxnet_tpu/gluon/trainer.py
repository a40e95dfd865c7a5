"""Gluon Trainer.

Reference: `python/mxnet/gluon/trainer.py:31` — owns the optimizer, wires
gradients through the kvstore (`_allreduce_grads` :385 with priority=-i for
comm/compute overlap) and applies updates.

TPU-native design: the update for ALL parameters is fused into one jitted
XLA program with donated buffers (the analogue of the reference's
multi-tensor `multi_sgd_mom_update` kernels + engine bulking) — one dispatch
per step instead of one per parameter.  Communication overlap comes from
XLA's async collectives instead of engine priorities: gradients of replicated
params over sharded batches are reduced *inside* the compiled
forward/backward, so `_allreduce_grads` is a no-op on the SPMD path and only
does explicit reductions for classic per-device-copy parallelism.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .. import observe as _observe
from ..base import MXNetError
from ..ndarray.ndarray import NDArray
from .. import optimizer as opt
from .. import telemetry as _telemetry
from ..kvstore import base as kvstore_base
from .parameter import Parameter

__all__ = ["Trainer"]


def _step_duration_histogram():
    # whole-step wall time as a proper histogram — the same distribution
    # the straggler policy sees via the KV steptime stamps, published so
    # the blackbox step lane and Prometheus read one source of truth
    # (docs/OBSERVABILITY.md)
    return _telemetry.histogram(
        "mxtpu_step_duration_seconds",
        "End-to-end Trainer.step wall time (allreduce + step-guards + "
        "optimizer update), including steps the guards skipped — the "
        "distribution the straggler policy's KV steptime stamps sample")


class Trainer:
    def __init__(self, params, optimizer, optimizer_params=None, kvstore="device",
                 compression_params=None, update_on_kvstore=None):
        if isinstance(params, (dict,)):
            params = [params[k] for k in sorted(params)]
        if not isinstance(params, (list, tuple)):
            raise ValueError("params must be a dict or list of Parameters")
        self._params = []
        self._param2idx = {}
        for i, param in enumerate(params):
            if not isinstance(param, Parameter):
                raise ValueError(f"element {i} is not a Parameter")
            self._param2idx[param.name] = i
            self._params.append(param)
        self._compression_params = compression_params
        self._scale = 1.0
        self._kvstore_type = kvstore
        self._kvstore = None
        self._kv_initialized = False
        self._update_on_kvstore = update_on_kvstore
        self._updaters = None
        self._fused_cache = {}
        self._states = None

        optimizer_params = optimizer_params or {}
        self._init_optimizer(optimizer, optimizer_params)

    def _init_optimizer(self, optimizer, optimizer_params):
        param_dict = {i: param for i, param in enumerate(self._params)}
        if isinstance(optimizer, opt.Optimizer):
            assert not optimizer_params, (
                "optimizer_params must be None if optimizer is an Optimizer "
                "instance")
            self._optimizer = optimizer
            self._optimizer.param_dict = param_dict
        else:
            self._optimizer = opt.create(optimizer, param_dict=param_dict,
                                         **optimizer_params)

    @property
    def optimizer(self):
        return self._optimizer

    @property
    def learning_rate(self):
        return self._optimizer.learning_rate

    def set_learning_rate(self, lr):
        self._optimizer.set_learning_rate(lr)

    # -- kvstore ----------------------------------------------------------
    def _init_kvstore(self):
        if self._kv_initialized:
            return
        kv = self._kvstore_type
        if kv is None or kv is False:
            self._kvstore = None
        elif isinstance(kv, kvstore_base.KVStoreBase):
            self._kvstore = kv
        elif isinstance(kv, str):
            # single device + local store: skip the round-trip entirely
            multi_device = any(len(p.list_ctx()) > 1 for p in self._params)
            multi_worker = jax.process_count() > 1
            if kv in ("local", "device") and not multi_device and not multi_worker:
                self._kvstore = None
            else:
                self._kvstore = kvstore_base.create(kv)
        else:
            raise MXNetError(f"invalid kvstore {kv!r}")
        if self._update_on_kvstore is None:
            self._update_on_kvstore = False  # optimizer runs in-worker on TPU
        if self._update_on_kvstore and self._kvstore is not None:
            if not self._kvstore.is_capable(kvstore_base.KVStoreBase.OPTIMIZER):
                raise ValueError(
                    f"kvstore {self._kvstore.type} does not support "
                    "update_on_kvstore")
            self._kvstore.set_optimizer(self._optimizer)
        if self._kvstore is not None and self._compression_params is not None:
            if not hasattr(self._kvstore, "set_gradient_compression"):
                raise ValueError(
                    f"kvstore {self._kvstore.type} does not support "
                    "gradient compression")
            self._kvstore.set_gradient_compression(self._compression_params)
        if self._kvstore is not None:
            # broadcast initial values so every device copy agrees
            # (reference trainer.py:164-174 kvstore init + pull)
            for i, param in enumerate(self._params):
                ctxs = param.list_ctx()
                if len(ctxs) > 1 and param._data is not None:
                    self._kvstore.broadcast(i, param.data(ctxs[0]),
                                            param.list_data())
        self._kv_initialized = True

    @property
    def kvstore(self):
        self._init_kvstore()
        return self._kvstore

    # -- states -----------------------------------------------------------
    def _init_states(self):
        if self._states is None:
            self._states = {}
            for i, param in enumerate(self._params):
                if param.grad_req != "null":
                    ctxs = param.list_ctx()
                    if len(ctxs) == 1:
                        self._states[i] = \
                            self._optimizer.create_state_multi_precision(
                                i, param.data())
                    else:
                        # one state per device copy (the reference keeps a
                        # per-device updater; sharing state would apply
                        # momentum N times per step)
                        self._states[i] = [
                            self._optimizer.create_state_multi_precision(i, w)
                            for w in param.list_data()]

    # -- step -------------------------------------------------------------
    def step(self, batch_size, ignore_stale_grad=False):
        """allreduce + optimizer update; ``batch_size`` normalizes gradients
        (reference trainer.py:334).  Both phases publish into the
        telemetry step-phase histogram and, while profiling, emit
        step-trace spans."""
        import time as _time

        t0 = _time.perf_counter()
        try:
            self._step(batch_size, ignore_stale_grad)
        finally:
            dt = _time.perf_counter() - t0
            _step_duration_histogram().observe(dt)
            _observe.record("step", "trainer.step", seconds=dt)

    def _step(self, batch_size, ignore_stale_grad):
        self._init_kvstore()
        self._optimizer.rescale_grad = self._scale / batch_size
        _telemetry.mark_step()
        with _telemetry.step_phase("allreduce"):
            self._allreduce_grads()
        # integrity step-guard (MXNET_KVSTORE_INTEGRITY=1): the digest
        # sideband flagged a corrupted bucket reduction — the reduced
        # grads are poisoned, so skip the update (params/states bitwise
        # untouched) exactly like a non-finite step.  The violation
        # counter was already ticked inside consume_integrity.
        consume = getattr(self._kvstore, "consume_integrity_violations",
                          None) if self._kvstore is not None else None
        violations = consume() if consume is not None else 0
        if violations > 0:
            from ..resilience import faultline as _faultline
            from ..resilience.policies import step_skip_counter
            step_skip_counter().inc()
            _observe.record("sentinel", "integrity_violation",
                            site="collective.dispatch",
                            violations=int(violations))
            _faultline.recovered("collective.dispatch", "bitflip")
            return
        # finite-grad step-guard (eager path): when amp attached a loss
        # scaler, consult it BEFORE the update — a poisoned step skips
        # the optimizer entirely (params/states untouched) and only backs
        # the scale off, mirroring the in-program guard in FusedTrainStep
        scaler = getattr(self, "_amp_loss_scaler", None)
        if scaler is not None and scaler.has_overflow(
                [p for p in self._params if p.grad_req != "null"]):
            from ..resilience import faultline as _faultline
            from ..resilience.policies import step_skip_counter
            step_skip_counter().inc()
            _faultline.recovered("train.grads", "nan_grad")
            scaler.update_scale(True)
            return
        with _telemetry.step_phase("optimizer"):
            self._update(ignore_stale_grad)
        if scaler is not None:
            scaler.update_scale(False)

    def allreduce_grads(self):
        self._init_kvstore()
        with _telemetry.step_phase("allreduce"):
            self._allreduce_grads()

    def _allreduce_grads(self):
        if self._kvstore is None:
            return
        pairs = [(i, param.list_grad())
                 for i, param in enumerate(self._params)
                 if param.grad_req != "null"]
        if not pairs:
            return
        from ..kvstore import bucketing as _bucketing
        if _bucketing.bucketing_enabled():
            # priority is load-bearing here: buckets are issued in
            # REVERSE registration order — backward produces last-layer
            # gradients first, so under jax's async dispatch the first
            # buckets ride the wire while the pack/unpack for later
            # buckets is still being enqueued (dispatch order IS the
            # overlap mechanism; kvstore/base.py pushpull docstring,
            # docs/DESIGN.md)
            self._kvstore.pushpull_list(pairs[::-1])
            return
        # MXNET_KVSTORE_BUCKETING=0: classic per-key collectives
        for i, grads in pairs:
            self._kvstore.pushpull(i, grads, priority=-i)

    def update(self, batch_size, ignore_stale_grad=False):
        self._init_kvstore()
        self._optimizer.rescale_grad = self._scale / batch_size
        with _telemetry.step_phase("optimizer"):
            self._update(ignore_stale_grad)

    def _update(self, ignore_stale_grad=False):
        if self._update_on_kvstore:
            for i, param in enumerate(self._params):
                if param.grad_req != "null":
                    self._kvstore.push(i, param.list_grad())
                    self._kvstore.pull(i, param.list_data())
            return
        self._init_states()
        fused = self._try_fused_update()
        if fused:
            return
        # per-parameter eager fallback (multi-device copies, odd optimizers)
        for i, param in enumerate(self._params):
            if param.grad_req == "null":
                continue
            self._eager_param_update(i, param)

    def _eager_param_update(self, i, param):
        from ..ndarray.sparse import RowSparseNDArray

        ws, gs = param.list_data(), param.list_grad()
        sts = self._states[i]
        if not isinstance(sts, list):
            sts = [sts]
        if len(sts) != len(ws):
            # device set changed since states were created (reset_ctx):
            # rebuild this parameter's states to match
            sts = [self._optimizer.create_state_multi_precision(i, w)
                   for w in ws]
            self._states[i] = sts if len(sts) > 1 else sts[0]
        _eager_updates_counter().inc()
        optimizer = self._optimizer
        if type(optimizer).update is not opt.Optimizer.update:
            # custom update() override: honor it verbatim, per device
            for dev_id, (w, g, st) in enumerate(zip(ws, gs, sts)):
                optimizer._set_current_context(dev_id)
                optimizer.update([i], [w], [g], [st])
            optimizer._set_current_context(0)
            return
        # host scalar work ONCE per param, not once per device copy (the
        # fused path packs lr/wd/t the same way); update counts stay
        # per-device (reference `Optimizer._set_current_context`)
        ts = []
        for dev_id in range(len(ws)):
            optimizer._set_current_context(dev_id)
            optimizer._update_count(i)
            ts.append(optimizer._index_update_count[i])
        optimizer._set_current_context(0)
        lr, wd = optimizer._get_lr(i), optimizer._get_wd(i)
        for w, g, st, t in zip(ws, gs, sts, ts):
            if isinstance(g, RowSparseNDArray):
                optimizer.update_sparse(w, g, st, lr, wd, t)
                continue
            gd = optimizer.preprocess_grad(g._data)
            new_w, new_st = optimizer.update_math(
                w._data, gd, tuple(s._data for s in _as_tuple(st)),
                lr, wd, t)
            w._rebind(new_w)
            for s_nd, s_new in zip(_as_tuple(st), _as_tuple(new_st)):
                s_nd._rebind(s_new)

    # -- the fused path ----------------------------------------------------
    def _try_fused_update(self):
        if getattr(self._optimizer, "supports_fused", True) is False:
            return False
        # row_sparse-grad params take the lazy eager path (reference
        # trainer.py routes row_sparse through sparse push/pull); the rest
        # still fuse into one XLA program
        sparse_idxs = [
            i for i, p in enumerate(self._params)
            if p.grad_req != "null"
            and getattr(p, "_grad_stype", "default") != "default"]
        idxs = [i for i, p in enumerate(self._params)
                if p.grad_req != "null" and len(p.list_ctx()) == 1
                and i not in sparse_idxs]
        if len(idxs) + len(sparse_idxs) != \
                sum(1 for p in self._params if p.grad_req != "null"):
            return False
        for i in sparse_idxs:
            self._eager_param_update(i, self._params[i])
        if not idxs:
            return True
        optimizer = self._optimizer
        key = (id(optimizer), tuple(idxs))
        fn = self._fused_cache.get(key)
        if fn is None:
            def fused(ws, gs, states, lrs, wds, ts, rescale, clip):
                new_ws, new_states = [], []
                for k, (w, g, st) in enumerate(zip(ws, gs, states)):
                    g = g * rescale
                    if clip is not None:
                        g = jnp.clip(g, -clip, clip)
                    nw, nst = optimizer.update_math(w, g, st, lrs[k], wds[k],
                                                    ts[k])
                    new_ws.append(nw)
                    new_states.append(nst)
                return new_ws, new_states

            fn = jax.jit(fused, donate_argnums=(0, 2), static_argnums=(7,))
            self._fused_cache[key] = fn

        ws = [self._params[i].data()._data for i in idxs]
        gs = [self._params[i].grad()._data for i in idxs]
        states = [tuple(s._data for s in _as_tuple(self._states[i]))
                  for i in idxs]
        lrs, wds, ts = [], [], []
        for i in idxs:
            optimizer._update_count(i)
            lrs.append(optimizer._get_lr(i))
            wds.append(optimizer._get_wd(i))
            ts.append(optimizer._index_update_count[i])
        # ship per-param scalars as three packed arrays: one host->device
        # transfer each, not 3*n_params tiny ones
        import numpy as onp
        lrs = jnp.asarray(onp.asarray(lrs, onp.float32))
        wds = jnp.asarray(onp.asarray(wds, onp.float32))
        ts = jnp.asarray(onp.asarray(ts, onp.float32))
        new_ws, new_states = fn(ws, gs, states, lrs, wds, ts,
                                jnp.float32(optimizer.rescale_grad),
                                optimizer.clip_gradient)
        for i, nw, nst in zip(idxs, new_ws, new_states):
            self._params[i].data()._rebind(nw)
            for s_nd, s_new in zip(_as_tuple(self._states[i]), _as_tuple(nst)):
                s_nd._rebind(s_new)
        return True

    # -- state I/O (reference trainer.py save_states/load_states) ----------
    def save_states(self, fname):
        self._init_states()
        updater = opt.Updater(self._optimizer)
        # multi-device params keep one state per copy; the copies are in
        # sync, so persist the first (the reference saves one updater too)
        updater.states = {
            i: (st[0] if isinstance(st, list) else st)
            for i, st in (self._states or {}).items()
        }
        with open(fname, "wb") as f:
            f.write(updater.get_states(dump_optimizer=False))

    def load_states(self, fname):
        updater = opt.Updater(self._optimizer)
        with open(fname, "rb") as f:
            updater.set_states(f.read())
        self._init_states()
        for i, st in updater.states.items():
            if i not in self._states:
                continue
            cur_entry = self._states[i]
            entries = cur_entry if isinstance(cur_entry, list) else [cur_entry]
            for entry in entries:  # every device copy gets the loaded state
                for cur, new in zip(_as_tuple(entry), _as_tuple(st)):
                    cur._rebind(new._data)


def _eager_updates_counter():
    return _telemetry.counter(
        "mxtpu_trainer_eager_updates_total",
        "Parameter updates taken on the per-parameter eager fallback "
        "path instead of the fused one-program update — a steadily "
        "rising value means the step silently de-fused (multi-device "
        "copies, row-sparse grads, or an optimizer without update_math)")


def _as_tuple(x):
    if x is None:
        return ()
    if isinstance(x, (tuple, list)):
        return tuple(x)
    return (x,)
