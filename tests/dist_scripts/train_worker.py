"""4-process SPMD training worker (VERDICT r1 #9).

The reference pattern: `tests/nightly/dist_sync_kvstore.py` — N local
processes run the same binary and assert value-deterministic results,
covering a normal key, a big-array key, and a compression key.  Here the
"keys" are: a full Gluon FusedTrainStep (loss+grads+update as one XLA
program over the 8-device 4-process mesh) checked against a local numpy
oracle, a 1M-element global psum, and the 2-bit compression reduce.
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"

import jax

# N local processes cannot share a chip: CPU-only whatever was inherited
jax.config.update("jax_platforms", "cpu")

import numpy as onp

import mxnet_tpu as mx  # noqa: F401  (bootstraps jax.distributed from env)
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def check_train_step_parity(rank):
    """3 FusedTrainStep SGD steps over the global mesh must match a local
    numpy simulation of the same math (every process asserts)."""
    from mxnet_tpu import gluon
    from mxnet_tpu.parallel import mesh as pmesh

    devs = jax.devices()
    mesh = pmesh.make_mesh({"dp": len(devs)}, devices=devs)

    mx.random.seed(7)
    net = gluon.nn.Dense(4, use_bias=True)
    net.initialize()

    class WithLoss(gluon.block.HybridBlock):
        def __init__(self, n):
            super().__init__()
            self.n = n

        def forward(self, x, y):
            d = self.n(x) - y
            return (d * d).mean()

    mod = WithLoss(net)
    rs = onp.random.RandomState(13)
    xs = [rs.rand(16, 5).astype("f") for _ in range(3)]
    ys = [rs.rand(16, 4).astype("f") for _ in range(3)]
    mod(mx.np.array(xs[0]), mx.np.array(ys[0]))  # shapes

    w0 = net.weight.data().asnumpy().copy()
    b0 = net.bias.data().asnumpy().copy()

    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.1})
    step = gluon.FusedTrainStep(mod, trainer, mesh=mesh, data_spec=P("dp"))
    for x, y in zip(xs, ys):
        loss = step(mx.np.array(x), mx.np.array(y), batch_size=1)
    final_loss = float(loss.asnumpy())

    # numpy oracle of the same math
    w, b = w0.copy(), b0.copy()
    for x, y in zip(xs, ys):
        pred = x @ w.T + b
        d = pred - y                       # (16, 4)
        gpred = 2 * d / d.size             # d(mean(d^2))/dpred
        gw = gpred.T @ x
        gb = gpred.sum(0)
        w -= 0.1 * gw
        b -= 0.1 * gb
        exp_loss = (d * d).mean()

    onp.testing.assert_allclose(net.weight.data().asnumpy(), w, rtol=1e-4,
                                atol=1e-5)
    onp.testing.assert_allclose(net.bias.data().asnumpy(), b, rtol=1e-4,
                                atol=1e-5)
    onp.testing.assert_allclose(final_loss, exp_loss, rtol=1e-4)
    print(f"rank {rank} TRAIN OK {final_loss:.6f}", flush=True)


def check_big_array(rank, nproc):
    """1M-element dp-sharded global reduction (the big-array key)."""
    devs = jax.devices()
    mesh = Mesh(onp.array(devs), ("dp",))
    n = 1_000_000
    per = n // nproc
    local = onp.full((per,), float(rank + 1), onp.float32)
    x = jax.make_array_from_process_local_data(
        NamedSharding(mesh, P("dp")), local)
    total = jax.jit(lambda a: a.sum(),
                    out_shardings=NamedSharding(mesh, P()))(x)
    got = float(total.addressable_shards[0].data)
    exp = sum(per * (r + 1) for r in range(nproc))
    assert got == exp, (got, exp)
    print(f"rank {rank} BIG OK {got}", flush=True)


def check_compression(rank):
    """2-bit compression reduce is deterministic and identical on every
    process (the compression key)."""
    from mxnet_tpu import kv
    from mxnet_tpu.ndarray.ndarray import NDArray

    store = kv.create("tpu_ici")
    store.set_gradient_compression({"type": "2bit", "threshold": 0.5})
    vals = [NDArray(onp.array([0.6, -0.7, 0.1, 0.0], onp.float32)),
            NDArray(onp.array([0.6, 0.7, -0.1, 0.0], onp.float32))]
    store.pushpull("k", vals)
    got = vals[0].asnumpy()
    exp = onp.array([1.0, 0.0, 0.0, 0.0], onp.float32)
    onp.testing.assert_allclose(got, exp)
    print(f"rank {rank} COMP OK", flush=True)


def check_hybrid_tp_dp(rank):
    """tp x dp hybrid mesh across the 4 processes (8 devices -> dp=4,
    tp=2): the tensor-parallel FusedTrainStep must produce the same
    trained weights as the local numpy oracle."""
    from jax.sharding import PartitionSpec as P2

    from mxnet_tpu import gluon
    from mxnet_tpu.parallel import mesh as pmesh

    devs = jax.devices()
    mesh = pmesh.make_mesh({"dp": len(devs) // 2, "tp": 2}, devices=devs)

    mx.random.seed(11)
    net = gluon.nn.Dense(8, use_bias=False)
    net.initialize()

    class WithLoss(gluon.block.HybridBlock):
        def __init__(self, n):
            super().__init__()
            self.n = n

        def forward(self, x, y):
            d = self.n(x) - y
            return (d * d).mean()

    mod = WithLoss(net)
    rs = onp.random.RandomState(17)
    x = rs.rand(16, 6).astype("f")
    y = rs.rand(16, 8).astype("f")
    mod(mx.np.array(x), mx.np.array(y))
    w0 = net.weight.data().asnumpy().copy()

    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.2})
    step = gluon.FusedTrainStep(
        mod, trainer, mesh=mesh,
        partition_rules=[(r".*weight", P2("tp", None))],
        data_spec=P2("dp"))
    loss = step(mx.np.array(x), mx.np.array(y), batch_size=1)

    pred = x @ w0.T
    d = pred - y
    gw = (2 * d / d.size).T @ x
    w_exp = w0 - 0.2 * gw
    onp.testing.assert_allclose(net.weight.data().asnumpy(), w_exp,
                                rtol=1e-4, atol=1e-5)
    onp.testing.assert_allclose(float(loss.asnumpy()), (d * d).mean(),
                                rtol=1e-4)
    print(f"rank {rank} HYBRID OK", flush=True)


def main():
    rank = jax.process_index()
    nproc = jax.process_count()
    assert nproc == 4, nproc
    assert len(jax.devices()) == 8, jax.devices()
    check_train_step_parity(rank)
    check_hybrid_tp_dp(rank)
    check_big_array(rank, nproc)
    check_compression(rank)
    check_failure_detection(rank)
    print(f"rank {rank} ALL OK", flush=True)




def check_failure_detection(rank):
    """Heartbeat liveness: all 4 ranks alive -> no dead nodes; a stale
    stamp -> that rank reported dead (reference get_dead_nodes)."""
    import time

    from mxnet_tpu import kv

    store = kv.create("tpu_ici")
    deadline = time.time() + 30
    dead = store.get_dead_nodes(timeout=60)
    while time.time() < deadline and dead:
        time.sleep(0.5)
        dead = store.get_dead_nodes(timeout=60)
    assert dead == [], dead
    # barrier (all ranks confirmed liveness) before rank 0 forges a stale
    # stamp -- otherwise another rank's alive-check could observe it
    import jax as _jax
    import numpy as _onp
    from jax.sharding import Mesh as _M, NamedSharding as _NS, \
        PartitionSpec as _P
    mesh = _M(_onp.array(_jax.devices()), ("dp",))
    one = _jax.make_array_from_process_local_data(
        _NS(mesh, _P("dp")), _onp.ones((2,), _onp.float32))
    _jax.jit(lambda a: a.sum(), out_shardings=_NS(mesh, _P()))(
        one).block_until_ready()
    # a stamp older than the timeout reads as dead (rank 0 forges one)
    if rank == 0:
        c = store._kv_client()
        try:
            c.key_value_delete("mxtpu/heartbeat/0")
        except Exception:
            pass
        c.key_value_set("mxtpu/heartbeat/0", repr(time.time() - 10_000))
        # two consecutive stale observations declare death (one missed
        # stamp is tolerated by the suspicion counter)
        store.get_dead_nodes(timeout=60)
        assert 0 in store.get_dead_nodes(timeout=60)
    store.close()
    print(f"rank {rank} LIVENESS OK", flush=True)


if __name__ == "__main__":
    main()
