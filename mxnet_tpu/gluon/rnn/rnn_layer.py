"""Fused multi-layer RNN/LSTM/GRU layers.

Reference: `python/mxnet/gluon/rnn/rnn_layer.py` over the fused RNN op
(`src/operator/rnn.cc:295`, cuDNN-backed on GPU).

TPU-native design: the whole stack (layers × directions × time) is ONE pure
function built from `lax.scan` — XLA compiles it to a single program whose
per-step matmuls hit the MXU; the input projection for all timesteps is
batched into one big matmul outside the scan (the same trick cuDNN uses).
Weight names/layout match the reference fused op (``l0_i2h_weight`` ...,
gates stacked [i, f, c, o] for LSTM / [r, z, n] for GRU), so checkpoints
map 1:1.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ... import numpy_extension as npx
from ...ndarray.ndarray import NDArray
from ...ops.invoke import invoke
from ..block import HybridBlock
from ..parameter import Parameter
from ..nn.basic_layers import _resolve_init

__all__ = ["RNN", "LSTM", "GRU"]


def _cell_step(mode, x_proj, h, c, h2h_w, h2h_b):
    """One timestep; x_proj is the precomputed input projection.  All
    gates come from one recurrent (H, ngates*H) matmul, then split."""
    g = x_proj + jnp.dot(h, h2h_w.T) + h2h_b
    if mode == "rnn_relu":
        nh = jax.nn.relu(g)
        return nh, c
    if mode == "rnn_tanh":
        nh = jnp.tanh(g)
        return nh, c
    hidden = h.shape[-1]
    if mode == "lstm":
        i, f, cc, o = jnp.split(g, 4, axis=-1)
        i = jax.nn.sigmoid(i)
        f = jax.nn.sigmoid(f)
        cc = jnp.tanh(cc)
        o = jax.nn.sigmoid(o)
        nc = f * c + i * cc
        nh = o * jnp.tanh(nc)
        return nh, nc
    if mode == "gru":
        # reference gru gates: reset, update, new
        rx, zx, nx = jnp.split(x_proj, 3, axis=-1)
        rh_all = jnp.dot(h, h2h_w.T) + h2h_b
        rh, zh, nh_ = jnp.split(rh_all, 3, axis=-1)
        r = jax.nn.sigmoid(rx + rh)
        z = jax.nn.sigmoid(zx + zh)
        n = jnp.tanh(nx + r * nh_)
        nh = (1 - z) * n + z * h
        return nh, c
    raise ValueError(mode)


def _run_single_direction(mode, x_tnc, h0, c0, i2h_w, i2h_b, h2h_w, h2h_b,
                          reverse=False):
    """scan over time for one layer/direction. x: (T, N, C)."""
    if reverse:
        x_tnc = jnp.flip(x_tnc, axis=0)
    # batch the input projection over all timesteps: one MXU matmul
    x_proj = jnp.einsum("tnc,gc->tng", x_tnc, i2h_w) + i2h_b

    def step(carry, xp):
        h, c = carry
        nh, nc = _cell_step(mode, xp, h, c, h2h_w, h2h_b)
        return (nh, nc), nh

    # One cell a loop iteration (`unroll` 1) over one fused gate matmul.
    # Set on jax 0.4.37 on another machine, where a single timing read
    # 1.24x for `unroll` 4 at `b32.h1024.t64` bf16; it was never repeated
    # and this machine has no number for it.  Re-pick it from runs of the
    # recurrent-scan cell (ROADMAP S5, W7) and name the ledger line here.
    (hT, cT), out = jax.lax.scan(step, (h0, c0), x_proj, unroll=1)
    if reverse:
        out = jnp.flip(out, axis=0)
    return out, hT, cT


def _gates(mode):
    return {"rnn_relu": 1, "rnn_tanh": 1, "lstm": 4, "gru": 3}[mode]


class _RNNLayer(HybridBlock):
    def __init__(self, mode, hidden_size, num_layers, layout, dropout,
                 bidirectional, input_size, i2h_weight_initializer,
                 h2h_weight_initializer, i2h_bias_initializer,
                 h2h_bias_initializer, dtype="float32", use_sequence_length=False,
                 **kwargs):
        super().__init__()
        assert layout in ("TNC", "NTC")
        self._mode = mode
        self._hidden_size = hidden_size
        self._num_layers = num_layers
        self._layout = layout
        self._dropout = dropout
        self._dir = 2 if bidirectional else 1
        self._input_size = input_size
        self._dtype = dtype
        self._use_sequence_length = use_sequence_length
        ng = _gates(mode)
        for layer in range(num_layers):
            for d in range(self._dir):
                suffix = ["l", "r"][d] + str(layer)
                in_sz = input_size if layer == 0 else hidden_size * self._dir
                self._register_param(
                    f"{suffix}_i2h_weight", (ng * hidden_size, in_sz),
                    i2h_weight_initializer, dtype)
                self._register_param(
                    f"{suffix}_h2h_weight", (ng * hidden_size, hidden_size),
                    h2h_weight_initializer, dtype)
                self._register_param(
                    f"{suffix}_i2h_bias", (ng * hidden_size,),
                    i2h_bias_initializer, dtype)
                self._register_param(
                    f"{suffix}_h2h_bias", (ng * hidden_size,),
                    h2h_bias_initializer, dtype)

    def _register_param(self, name, shape, init, dtype):
        p = Parameter(name, shape=shape, init=_resolve_init(init),
                      allow_deferred_init=True, dtype=dtype)
        setattr(self, name, p)

    def cast(self, dtype):
        # reference `_RNNLayer.cast` also retargets self._dtype: without
        # it begin_state() keeps emitting float32 initial states, the
        # scan carry promotes every gate op, and layer >= 1 of a bf16
        # model silently computes in f32
        super().cast(dtype)
        self._dtype = dtype

    def state_info(self, batch_size=0):
        raise NotImplementedError

    def begin_state(self, batch_size=0, func=None, ctx=None, **kwargs):
        from ... import numpy as mxnp
        states = []
        for _ in range(1 if self._mode != "lstm" else 2):
            states.append(mxnp.zeros(
                (self._num_layers * self._dir, batch_size, self._hidden_size),
                ctx=ctx, dtype=self._dtype))
        return states if self._mode == "lstm" else states

    def _finish_deferred(self, in_sz0):
        ng = _gates(self._mode)
        for layer in range(self._num_layers):
            for d in range(self._dir):
                suffix = ["l", "r"][d] + str(layer)
                in_sz = in_sz0 if layer == 0 else self._hidden_size * self._dir
                w = getattr(self, f"{suffix}_i2h_weight")
                if w.shape[1] == 0:
                    w.shape = (ng * self._hidden_size, in_sz)
                for pname in ("i2h_weight", "h2h_weight", "i2h_bias",
                              "h2h_bias"):
                    p = getattr(self, f"{suffix}_{pname}")
                    if p._data is None:
                        p.finish_deferred_init()

    def forward(self, inputs, states=None, sequence_length=None):
        layout = self._layout
        if layout == "NTC":
            inputs = inputs.swapaxes(0, 1)
        t, n, c = inputs.shape
        self._finish_deferred(c)

        explicit_states = states is not None
        if states is None:
            states = self.begin_state(batch_size=n, ctx=inputs.ctx)
        if isinstance(states, NDArray):
            states = [states]
        mode = self._mode
        num_layers = self._num_layers
        ndir = self._dir
        hidden = self._hidden_size
        dropout = self._dropout
        from ...ops.invoke import is_training
        training = is_training()
        from ... import random as _rng
        key = _rng.new_key() if (dropout and training) else None

        weights = []
        for layer in range(num_layers):
            for d in range(ndir):
                suffix = ["l", "r"][d] + str(layer)
                weights.extend([
                    getattr(self, f"{suffix}_i2h_weight").data(),
                    getattr(self, f"{suffix}_i2h_bias").data(),
                    getattr(self, f"{suffix}_h2h_weight").data(),
                    getattr(self, f"{suffix}_h2h_bias").data(),
                ])

        def fused(x, h0_all, c0_all, *flat_w):
            outs = x
            h_list, c_list = [], []
            wi = 0
            for layer in range(num_layers):
                layer_outs = []
                for d in range(ndir):
                    i2h_w, i2h_b, h2h_w, h2h_b = flat_w[wi:wi + 4]
                    wi += 4
                    sidx = layer * ndir + d
                    out, hT, cT = _run_single_direction(
                        mode, outs, h0_all[sidx], c0_all[sidx],
                        i2h_w, i2h_b, h2h_w, h2h_b, reverse=(d == 1))
                    layer_outs.append(out)
                    h_list.append(hT)
                    c_list.append(cT)
                outs = layer_outs[0] if ndir == 1 else jnp.concatenate(
                    layer_outs, axis=-1)
                if dropout and training and layer < num_layers - 1:
                    keep = 1.0 - dropout
                    mask = jax.random.bernoulli(
                        jax.random.fold_in(key, layer), keep, outs.shape)
                    outs = jnp.where(mask, outs / keep, 0).astype(outs.dtype)
            return outs, jnp.stack(h_list), jnp.stack(c_list)

        h0 = states[0]
        c0 = states[1] if mode == "lstm" else states[0]
        out, hn, cn = invoke(fused, (inputs, h0, c0) + tuple(weights),
                             name=f"rnn_{mode}" + ("_bi" if ndir == 2
                                                   else ""))
        if layout == "NTC":
            out = out.swapaxes(0, 1)
        if not explicit_states:
            return out
        if mode == "lstm":
            return out, [hn, cn]
        return out, hn

    def __repr__(self):
        return (f"{type(self).__name__}({self._hidden_size}, "
                f"num_layers={self._num_layers}, "
                f"bidirectional={self._dir == 2})")


class RNN(_RNNLayer):
    def __init__(self, hidden_size, num_layers=1, activation="relu",
                 layout="TNC", dropout=0, bidirectional=False, input_size=0,
                 i2h_weight_initializer=None, h2h_weight_initializer=None,
                 i2h_bias_initializer="zeros", h2h_bias_initializer="zeros",
                 dtype="float32", **kwargs):
        super().__init__("rnn_relu" if activation == "relu" else "rnn_tanh",
                         hidden_size, num_layers, layout, dropout,
                         bidirectional, input_size, i2h_weight_initializer,
                         h2h_weight_initializer, i2h_bias_initializer,
                         h2h_bias_initializer, dtype, **kwargs)


class LSTM(_RNNLayer):
    def __init__(self, hidden_size, num_layers=1, layout="TNC", dropout=0,
                 bidirectional=False, input_size=0,
                 i2h_weight_initializer=None, h2h_weight_initializer=None,
                 i2h_bias_initializer="zeros", h2h_bias_initializer="zeros",
                 dtype="float32", **kwargs):
        super().__init__("lstm", hidden_size, num_layers, layout, dropout,
                         bidirectional, input_size, i2h_weight_initializer,
                         h2h_weight_initializer, i2h_bias_initializer,
                         h2h_bias_initializer, dtype, **kwargs)


class GRU(_RNNLayer):
    def __init__(self, hidden_size, num_layers=1, layout="TNC", dropout=0,
                 bidirectional=False, input_size=0,
                 i2h_weight_initializer=None, h2h_weight_initializer=None,
                 i2h_bias_initializer="zeros", h2h_bias_initializer="zeros",
                 dtype="float32", **kwargs):
        super().__init__("gru", hidden_size, num_layers, layout, dropout,
                         bidirectional, input_size, i2h_weight_initializer,
                         h2h_weight_initializer, i2h_bias_initializer,
                         h2h_bias_initializer, dtype, **kwargs)
