"""ResNet ImageNet training (reference
`example/image-classification/train_imagenet.py` shape, BASELINE configs
2-3): model-zoo network + ImageRecord pipeline + data-parallel Trainer.

Point --rec-train at an im2rec pack (tools/im2rec.py); without one the
script trains on synthetic batches so it runs anywhere.  Multi-device
data parallelism follows the classic pattern: initialize(ctx=...) +
split_and_load + kvstore.
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


import numpy as onp

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon
from mxnet_tpu.gluon.model_zoo import vision
from mxnet_tpu.gluon.utils import split_and_load


def parse():
    p = argparse.ArgumentParser()
    p.add_argument("--model", default="resnet50_v1")
    p.add_argument("--rec-train", default=None,
                   help=".rec file from tools/im2rec.py")
    p.add_argument("--batch-size", type=int, default=64)
    p.add_argument("--iters", type=int, default=20)
    p.add_argument("--lr", type=float, default=0.1)
    p.add_argument("--dtype", default="float32",
                   choices=["float32", "bfloat16"])
    p.add_argument("--kv-store", default="device")
    p.add_argument("--num-devices", type=int, default=1)
    return p.parse_args()


_MEAN = onp.array([123.68, 116.779, 103.939], onp.float32)
_STD = onp.array([58.393, 57.12, 57.375], onp.float32)


def batches(args, ctxs):
    if args.rec_train:
        # native C++ pipeline (src/image_pipeline.cc): GIL-free JPEG
        # decode threads -> NHWC uint8; normalize on DEVICE so XLA fuses
        # it into the first conv (host normalization would halve
        # throughput).  Falls back to the PIL ImageIter if libjpeg is
        # unavailable.
        try:
            it = mx.io.ImageRecordIter(
                path_imgrec=args.rec_train, batch_size=args.batch_size,
                data_shape=(3, 224, 224), resize=256, rand_crop=True,
                rand_mirror=True, shuffle=True, layout="NHWC")
        except (RuntimeError, IOError):
            it = mx.image.ImageIter(
                args.batch_size, (3, 224, 224), path_imgrec=args.rec_train,
                shuffle=True,
                aug_list=mx.image.CreateAugmenter((3, 224, 224), resize=256,
                                                  rand_crop=True,
                                                  rand_mirror=True,
                                                  mean=True, std=True))
            while True:
                it.reset()
                for b in it:
                    yield b.data[0].astype(args.dtype), b.label[0]
        # prefetch-to-device double buffering (io/prefetch.py): the H2D
        # transfer for batch N+1 rides the wire while step N computes —
        # the step-time law becomes max(feed, compute), not the sum
        pf = mx.io.DevicePrefetcher(it, depth=3, dtypes=(None, onp.int32))
        mean = mx.np.array(_MEAN)
        std = mx.np.array(_STD)
        while True:
            for data, labels in pf:
                x = ((data.astype("float32") - mean) / std) \
                    .astype(args.dtype)
                # NHWC -> NCHW for the reference-layout model zoo
                yield mx.np.transpose(x, (0, 3, 1, 2)), labels
            pf.reset()
    else:
        x = mx.np.array(onp.random.uniform(-1, 1,
                                           (args.batch_size, 3, 224, 224)),
                        dtype=args.dtype)
        y = mx.np.array(onp.random.randint(0, 1000, (args.batch_size,)),
                        dtype="int32")
        while True:
            yield x, y


def main():
    args = parse()
    # devices of the default accelerator (the CPU mesh when there is none)
    ctxs = [mx.Context(mx.current_context().device_type, i)
            for i in range(args.num_devices)]
    net = getattr(vision, args.model)()
    net.initialize(init=mx.init.Xavier(), ctx=ctxs)
    if args.dtype == "bfloat16":
        net.cast("bfloat16")
    net.hybridize(static_alloc=True)

    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": args.lr, "momentum": 0.9},
                            kvstore=args.kv_store)
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()

    speed = mx.callback.Speedometer(args.batch_size, frequent=10)
    from collections import namedtuple
    P = namedtuple("P", ["epoch", "nbatch", "eval_metric"])

    gen = batches(args, ctxs)
    for i in range(args.iters):
        x, y = next(gen)
        xs = split_and_load(x, ctxs)
        ys = split_and_load(y, ctxs)
        with autograd.record():
            losses = [loss_fn(net(xb), yb).mean() for xb, yb in zip(xs, ys)]
        autograd.backward(losses)
        trainer.step(args.batch_size)
        speed(P(0, i + 1, None))
    print("final loss:",
          sum(float(l.asnumpy()) for l in losses) / len(losses))


if __name__ == "__main__":
    main()
