"""resnet50_v1: the zoo model with its loss under SGD+momentum in one
`FusedTrainStep`, built from resnet50_v1.json (construction copied from
`bench.py::_bench_at_batch` and `chip_smoke.py::_resnet_step`)."""
import jax
import jax.numpy as jnp

# Model FLOPs of one training step: 2 FLOPs per multiply-add.  Forward is
# 2 * prod(weight shape) * output positions, summed over every Conv2D and
# Dense as the shape-settling forward of one image runs them; backward is
# twice the forward (one matmul for the data gradient, one for the weight's),
# so a step is 3 * forward * images.  BatchNorm, ReLU, pooling, the loss and
# the optimizer are not counted.
FLOP_CONVENTION = "2 FLOPs per multiply-add; conv+dense only; step = 3 x forward"


def build(cfg):
    """(model with its loss, trainer).  Parameters come from the model's own
    initializer under the seed the runner has set."""
    import mxnet_tpu as mx
    from mxnet_tpu.gluon import loss as gloss, nn
    from mxnet_tpu.gluon.block import HybridBlock
    from mxnet_tpu.gluon.model_zoo import vision

    class NetWithLoss(HybridBlock):
        def __init__(self, net, loss_fn):
            super().__init__()
            self.net = net
            self.loss_fn = loss_fn

        def forward(self, x, y):
            return self.loss_fn(self.net(x), y)

    net = getattr(vision, cfg["model_zoo"])(classes=cfg["classes"])
    net.initialize(init=getattr(mx.init, cfg["initializer"])())
    net.cast(cfg["dtype"])

    flops, handles = [], []

    def count(block, _inputs, out):
        positions = out.shape[2] * out.shape[3] if len(out.shape) == 4 else 1
        n = 2 * positions
        for d in block.weight.shape:
            n *= d
        flops.append(n)

    net.apply(lambda b: handles.append(b.register_forward_hook(count))
              if isinstance(b, (nn.Conv2D, nn.Dense)) else None)
    # the zoo model infers its channel counts: one eager forward of ONE image
    # settles every deferred shape (about 170 small programs, cached)
    size = cfg["image_size"]
    net(mx.np.zeros((1, 3, size, size), dtype=cfg["dtype"]))
    for h in handles:
        h.detach()

    mod = NetWithLoss(net, gloss.SoftmaxCrossEntropyLoss())
    mod.forward_flops_per_image = sum(flops)
    return mod, mx.gluon.Trainer(net.collect_params(), cfg["optimizer"],
                                 dict(cfg["optimizer_params"]), kvstore="device")


def make_ring(cfg, cell, chips, seed, sharding):
    """`ring` batches of `batch` images per chip, made on the device in one
    jitted call from the seed: [((images, labels), images in the batch)]."""
    from mxnet_tpu.ndarray.ndarray import NDArray
    n, b, size = cell["ring"], cell["batch"] * chips, cfg["image_size"]

    def make(key):
        kx, ky = jax.random.split(key)
        x = jax.random.uniform(kx, (n, b, 3, size, size), jnp.dtype(cfg["dtype"]), -1, 1)
        y = jax.random.randint(ky, (n, b), 0, cfg["classes"], jnp.int32)
        return [(x[i], y[i]) for i in range(n)]

    made = jax.jit(make, out_shardings=sharding)(jax.random.key(seed))
    return [((NDArray(x), NDArray(y)), b) for x, y in made]


def flops_per_step(cfg, cell, chips, mod):
    return 3 * mod.forward_flops_per_image * cell["batch"] * chips
