"""Multi-chip SPMD training through the Gluon API.

Reference shape: `example/distributed_training*` (dist kvstore / horovod
launch scripts).  The TPU path needs no launcher for a single host: pass a
mesh to `gluon.FusedTrainStep` and the one-program-per-step training loop
runs data/tensor-parallel with XLA inserting the collectives over ICI.

Run on real chips, or simulate a pod on CPU:
    JAX_PLATFORMS=cpu \
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    python examples/distributed/spmd_train.py --dp 4 --tp 2
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))

import numpy as onp

import mxnet_tpu as mx
from mxnet_tpu import gluon
from mxnet_tpu.gluon import nn
from mxnet_tpu.parallel import mesh as pmesh


class NetWithLoss(gluon.HybridBlock):
    def __init__(self, net):
        super().__init__()
        self.net = net
        self.loss = gluon.loss.SoftmaxCrossEntropyLoss()

    def forward(self, x, y):
        return self.loss(self.net(x), y)


def main():
    from jax.sharding import PartitionSpec as P

    p = argparse.ArgumentParser()
    p.add_argument("--dp", type=int, default=-1,
                   help="data-parallel ways (-1: all remaining chips)")
    p.add_argument("--tp", type=int, default=1, help="tensor-parallel ways")
    p.add_argument("--batch-size", type=int, default=64)
    p.add_argument("--iters", type=int, default=20)
    args = p.parse_args()

    mesh = pmesh.make_mesh({"dp": args.dp, "tp": args.tp})
    print(f"mesh: {dict(mesh.shape)}")

    net = nn.HybridSequential()
    net.add(nn.Dense(256, activation="relu"))
    net.add(nn.Dense(256, activation="relu"))
    net.add(nn.Dense(10))
    net.initialize(init=mx.init.Xavier())
    mod = NetWithLoss(net)

    onp.random.seed(0)
    X = onp.random.randn(args.batch_size, 64).astype(onp.float32)
    Y = onp.random.randint(0, 10, (args.batch_size,))
    x = mx.np.array(X)
    y = mx.np.array(Y, dtype="int32")
    mod(x, y)   # materialize shapes

    trainer = gluon.Trainer(net.collect_params(), "adam",
                            {"learning_rate": 1e-3})
    # Megatron-style: first Dense column-parallel, rest replicated
    step = gluon.FusedTrainStep(
        mod, trainer, mesh=mesh,
        partition_rules=[(r"net\.0\.weight", P("tp", None))],
        data_spec=P("dp"))

    for i in range(args.iters):
        loss = step(x, y, batch_size=args.batch_size)
        if i % 5 == 0 or i == args.iters - 1:
            print(f"iter {i:3d}  loss {float(loss.asnumpy().mean()):.4f}")

    w = net.collect_params()["0.weight"].data()._data
    print("first-layer weight sharding:", w.sharding)


if __name__ == "__main__":
    main()
