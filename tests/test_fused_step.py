"""FusedTrainStep must be numerically identical to record/backward/step.

Reference analogue: CachedOp static vs dynamic execution equivalence
(`tests/python/unittest/test_gluon.py` hybridize checks).
"""
import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu.gluon import FusedTrainStep, Trainer, loss as gloss, nn
from mxnet_tpu.gluon.block import HybridBlock
from mxnet_tpu.test_utils import assert_almost_equal


class _NetWithLoss(HybridBlock):
    def __init__(self, net, loss_fn):
        super().__init__()
        self.net = net
        self.loss_fn = loss_fn

    def forward(self, x, y):
        return self.loss_fn(self.net(x), y)


def _make(seed, with_bn=True):
    onp.random.seed(seed)
    net = nn.HybridSequential()
    # no conv bias before BN: BN cancels mean shifts, leaving the bias with
    # a ~0 gradient whose Adam-normalized update amplifies float noise into
    # divergent-but-equally-valid trajectories between compiled programs
    net.add(nn.Conv2D(4, kernel_size=3, padding=1, use_bias=not with_bn))
    if with_bn:
        net.add(nn.BatchNorm())
    net.add(nn.Activation("relu"))
    net.add(nn.Dense(8))
    net.initialize(init=mx.init.Xavier())
    return _NetWithLoss(net, gloss.SoftmaxCrossEntropyLoss()), net


@pytest.mark.parametrize("opt,kw", [
    ("sgd", {"learning_rate": 0.1, "momentum": 0.9}),
    ("adam", {"learning_rate": 0.01}),
])
def test_fused_step_matches_eager(opt, kw):
    x_np = onp.random.uniform(-1, 1, (8, 3, 6, 6)).astype(onp.float32)
    y_np = onp.random.randint(0, 8, (8,))

    mod_a, net_a = _make(0)
    mod_b, net_b = _make(0)   # identical init (same seed + init rngs)
    x = mx.np.array(x_np)
    y = mx.np.array(y_np, dtype="int32")
    mod_a(x, y)               # materialize deferred shapes (inference mode)
    mod_b(x, y)
    # force identical weights
    pa, pb = net_a.collect_params(), net_b.collect_params()
    for k in pa:
        pb[k].set_data(mx.np.array(pa[k].data().asnumpy()))

    tr_a = Trainer(pa, opt, dict(kw))
    tr_b = Trainer(pb, opt, dict(kw))
    fused = FusedTrainStep(mod_b, tr_b)

    losses_a, losses_b = [], []
    for _ in range(3):
        with mx.autograd.record():
            la = mod_a(x, y)
        la.backward()
        tr_a.step(8)
        losses_a.append(la.asnumpy())
        lb = fused(x, y, batch_size=8)
        losses_b.append(lb.asnumpy())

    for la, lb in zip(losses_a, losses_b):
        assert_almost_equal(la, lb, rtol=1e-4, atol=1e-5)
    for k in pa:
        assert_almost_equal(pa[k].data().asnumpy(), pb[k].data().asnumpy(),
                            rtol=1e-4, atol=1e-5,
                            names=(f"eager:{k}", f"fused:{k}"))


def test_fused_step_updates_batchnorm_stats():
    mod, net = _make(1, with_bn=True)
    tr = Trainer(net.collect_params(), "sgd", {"learning_rate": 0.1})
    fused = FusedTrainStep(mod, tr)
    x = mx.np.array(onp.random.uniform(-1, 1, (8, 3, 6, 6)).astype(onp.float32))
    y = mx.np.array(onp.random.randint(0, 8, (8,)), dtype="int32")
    fused(x, y, batch_size=8)   # first step finishes deferred shape init
    params = net.collect_params()
    rm_key = [k for k in params if "running_mean" in k][0]
    before = params[rm_key].data().asnumpy().copy()
    for _ in range(3):
        fused(x, y, batch_size=8)
    after = params[rm_key].data().asnumpy()
    assert onp.abs(after - before).max() > 0


def test_fused_step_rejects_statless_optimizer():
    class Weird(mx.optimizer.Optimizer):
        supports_fused = False

        def create_state(self, index, weight):
            return None

        def update(self, indices, weights, grads, states):
            pass

    mod, net = _make(2, with_bn=False)
    tr = Trainer(net.collect_params(), Weird())
    fused = FusedTrainStep(mod, tr)
    x = mx.np.array(onp.zeros((2, 3, 6, 6), onp.float32))
    y = mx.np.array(onp.zeros((2,), onp.int32))
    with pytest.raises(ValueError, match="update_math"):
        fused(x, y, batch_size=2)


def test_fused_step_with_frozen_subset():
    # trainer manages only the Dense tail; conv stays frozen (constant)
    mod, net = _make(3, with_bn=False)
    dense = [c for c in net._children.values()
             if type(c).__name__ == "Dense"][0]
    x = mx.np.array(onp.random.uniform(-1, 1, (4, 3, 6, 6)).astype(onp.float32))
    y = mx.np.array(onp.random.randint(0, 8, (4,)), dtype="int32")
    mod(x, y)
    conv_w = [p for k, p in net.collect_params().items() if "0." in k][0]
    before = conv_w.data().asnumpy().copy()
    tr = Trainer(dense.collect_params(), "sgd", {"learning_rate": 0.5})
    fused = FusedTrainStep(mod, tr)
    fused(x, y, batch_size=4)
    fused(x, y, batch_size=4)
    assert_almost_equal(conv_w.data().asnumpy(), before, atol=0)  # frozen
    dw = dense.weight.data().asnumpy()
    assert onp.abs(dw).max() > 0


@pytest.mark.parametrize("bn", [False, True], ids=["xla", "bn"])
def test_fused_step_spmd_dp_matches_single_device(bn):
    """``bn``: BatchNorm in the net.  Its statistics, forward and backward,
    are sums over the global batch; under the mesh that axis is sharded and
    the partitioner all-reduces them, which must equal the one-device
    step."""
    from mxnet_tpu.parallel import mesh as pmesh

    x_np = onp.random.RandomState(7).uniform(-1, 1, (16, 3, 6, 6)) \
        .astype(onp.float32)
    y_np = onp.random.RandomState(8).randint(0, 8, (16,))

    losses = {}
    finals = {}
    init_weights = None
    for mode in ("single", "dp8"):
        mod, net = _make(9, with_bn=bn)
        x = mx.np.array(x_np)
        y = mx.np.array(y_np, dtype="int32")
        mod(x, y)
        params = net.collect_params()
        if init_weights is None:
            init_weights = {k: p.data().asnumpy() for k, p in params.items()}
        else:
            for k, p in params.items():
                p.set_data(mx.np.array(init_weights[k]))
        tr = Trainer(net.collect_params(), "sgd",
                     {"learning_rate": 0.1, "momentum": 0.9})
        mesh = None if mode == "single" else pmesh.make_mesh({"dp": 8})
        fused = FusedTrainStep(mod, tr, mesh=mesh)
        ls = [fused(x, y, batch_size=16).asnumpy() for _ in range(3)]
        losses[mode] = ls
        finals[mode] = {k: p.data().asnumpy()
                        for k, p in net.collect_params().items()}
        if mesh is not None:
            # parameters stay resident on the mesh
            w = [p for p in net.collect_params().values()][0].data()._data
            assert len(w.sharding.device_set) == 8

    for la, lb in zip(losses["single"], losses["dp8"]):
        assert_almost_equal(la, lb, rtol=1e-4, atol=1e-5)
    for k in finals["single"]:
        assert_almost_equal(finals["single"][k], finals["dp8"][k],
                            rtol=1e-4, atol=1e-5, names=(f"1dev:{k}",
                                                         f"dp8:{k}"))


def test_fused_step_spmd_tensor_parallel_rules():
    from jax.sharding import PartitionSpec as P

    from mxnet_tpu.parallel import mesh as pmesh

    mod, net = _make(10, with_bn=False)
    rng = onp.random.RandomState(10)
    x = mx.np.array(rng.uniform(-1, 1, (8, 3, 6, 6)).astype(onp.float32))
    y = mx.np.array(rng.randint(0, 8, (8,)), dtype="int32")
    mod(x, y)
    tr = Trainer(net.collect_params(), "sgd", {"learning_rate": 0.1})
    mesh = pmesh.make_mesh({"dp": 4, "tp": 2})
    rules = [(r".*Dense.*weight|.*2\.weight", P("tp", None))]
    fused = FusedTrainStep(mod, tr, mesh=mesh,
                           partition_rules=rules,
                           data_spec=P("dp"))
    l0 = fused(x, y, batch_size=8)
    l1 = fused(x, y, batch_size=8)
    assert onp.isfinite(l0.asnumpy()).all()
    assert l1.asnumpy().mean() < l0.asnumpy().mean()  # it is learning


def test_fused_step_spmd_broadcastable_extra_input():
    # a (1, F) auxiliary input must replicate, not crash on dp sharding
    from mxnet_tpu.parallel import mesh as pmesh

    class WithBias(HybridBlock):
        def __init__(self):
            super().__init__()
            self.d = nn.Dense(4)

        def forward(self, x, shift, y):
            out = self.d(x + shift)
            return gloss.SoftmaxCrossEntropyLoss()(out, y)

    mod = WithBias()
    mod.initialize()
    x = mx.np.array(onp.random.randn(8, 5).astype(onp.float32))
    shift = mx.np.array(onp.random.randn(1, 5).astype(onp.float32))
    y = mx.np.array(onp.random.randint(0, 4, (8,)), dtype="int32")
    mod(x, shift, y)
    tr = Trainer(mod.collect_params(), "sgd", {"learning_rate": 0.1})
    fused = FusedTrainStep(mod, tr, mesh=pmesh.make_mesh({"dp": 8}))
    loss = fused(x, shift, y, batch_size=8)
    assert onp.isfinite(loss.asnumpy()).all()


def test_fused_step_spmd_rank2_data_spec_with_1d_labels():
    # a 2-entry data_spec must truncate for rank-1 inputs instead of crashing
    from jax.sharding import PartitionSpec as P

    from mxnet_tpu.parallel import mesh as pmesh

    class MLP(HybridBlock):
        def __init__(self):
            super().__init__()
            self.d = nn.Dense(4)

        def forward(self, x, y):
            return gloss.SoftmaxCrossEntropyLoss()(self.d(x), y)

    mod = MLP()
    mod.initialize()
    rng = onp.random.RandomState(11)
    x = mx.np.array(rng.randn(8, 6).astype(onp.float32))
    y = mx.np.array(rng.randint(0, 4, (8,)), dtype="int32")
    mod(x, y)
    tr = Trainer(mod.collect_params(), "sgd", {"learning_rate": 0.1})
    mesh = pmesh.make_mesh({"dp": 4, "tp": 2})
    fused = FusedTrainStep(mod, tr, mesh=mesh, data_spec=P("dp", "tp"))
    loss = fused(x, y, batch_size=8)
    assert onp.isfinite(loss.asnumpy()).all()


def test_fused_step_prng_counter_survives_float_special_zone():
    """ADVICE r5: the PRNG stream counter now ships as its own int32
    array instead of int32 bits viewed as float32 — counters in the
    inf/NaN bitpattern zone (>= 0x7F800000) must reach fold_in exactly.
    Two adjacent sNaN-zone counters must produce different dropout
    masks (the old float channel could canonicalize both onto the same
    quiet-NaN pattern), and the same counter must reproduce bit-exactly."""
    from mxnet_tpu import random as _rng

    def build(seed):
        onp.random.seed(seed)
        net = nn.HybridSequential()
        net.add(nn.Dense(16))
        net.add(nn.Dropout(0.5))
        net.add(nn.Dense(4))
        net.initialize(init=mx.init.Xavier())
        return _NetWithLoss(net, gloss.SoftmaxCrossEntropyLoss()), net

    x = mx.np.array(onp.random.RandomState(0).uniform(-1, 1, (8, 6))
                    .astype(onp.float32))
    y = mx.np.array(onp.random.RandomState(1).randint(0, 4, (8,)),
                    dtype="int32")

    def loss_at_counter(counter):
        mx.random.seed(5)  # identical init draws across builds
        mod, net = build(3)
        tr = Trainer(net.collect_params(), "sgd", {"learning_rate": 0.0})
        fused = FusedTrainStep(mod, tr)
        fused(x, y, batch_size=8)  # setup/compile consumes stream draws
        _rng._state.counter = counter
        return float(onp.asarray(fused(x, y, batch_size=8).asnumpy()).sum())

    base = 0x7F800000  # first f32-inf bitpattern
    snan_a = loss_at_counter(base + 1)
    snan_b = loss_at_counter(base + 2)
    snan_a2 = loss_at_counter(base + 1)
    assert snan_a == snan_a2, "same counter must reproduce the same mask"
    assert snan_a != snan_b, \
        "adjacent NaN-zone counters collapsed to one dropout mask"
