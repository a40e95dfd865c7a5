"""LSTM LM (BASELINE config 5), bucketing iterator, and im2rec tests."""
import os
import subprocess
import sys

import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon
from mxnet_tpu.io import BucketSentenceIter
from mxnet_tpu.models import RNNModel

VOCAB = 30


def _batch_loss(model, loss_fn, data, label, state):
    logits, state = model(data, state)
    return loss_fn(logits, label).mean(), state


def test_rnn_lm_forward_shapes():
    m = RNNModel(VOCAB, num_embed=16, num_hidden=16, num_layers=2)
    m.initialize()
    x = mx.np.array(onp.random.randint(0, VOCAB, (7, 4)), dtype="int32")
    logits = m(x)
    assert logits.shape == (7, 4, VOCAB)
    state = m.begin_state(batch_size=4)
    logits, new_state = m(x, state)
    assert logits.shape == (7, 4, VOCAB)
    assert len(new_state) == 2  # lstm h, c


def test_rnn_lm_tied_weights():
    m = RNNModel(VOCAB, num_embed=16, num_hidden=16, tie_weights=True)
    m.initialize()
    x = mx.np.array(onp.random.randint(0, VOCAB, (5, 2)), dtype="int32")
    assert m(x).shape == (5, 2, VOCAB)
    # no separate decoder parameters exist
    names = list(m.collect_params())
    assert not any("decoder" in n for n in names)
    with pytest.raises(ValueError):
        RNNModel(VOCAB, num_embed=8, num_hidden=16, tie_weights=True)


def test_rnn_lm_trains():
    """A few steps on a repeating sequence must drop the loss (config 5
    end-to-end: scan-lowered LSTM + autograd + Trainer)."""
    onp.random.seed(0)
    m = RNNModel(VOCAB, num_embed=32, num_hidden=32, num_layers=1,
                 dropout=0.0)
    m.initialize()
    trainer = gluon.Trainer(m.collect_params(), "adam",
                            {"learning_rate": 0.01})
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    seq = onp.arange(64) % VOCAB
    data = mx.np.array(seq[:-1].reshape(7, 9), dtype="int32")
    label = mx.np.array(seq[1:].reshape(7, 9), dtype="int32")
    losses = []
    for _ in range(30):
        with autograd.record():
            logits = m(data)
            loss = loss_fn(logits, label).mean()
        loss.backward()
        trainer.step(1)
        losses.append(float(loss.asnumpy()))
    assert losses[-1] < losses[0] * 0.5, losses[::10]


def test_lstm_cast_bf16_both_layers_scan_in_bf16(monkeypatch):
    """`_RNNLayer.cast` must retarget self._dtype (reference behavior):
    otherwise begin_state() emits float32 initial states, the scan carry
    promotes every gate op, and layer >= 1 of a bf16 model silently
    computes in f32."""
    import jax
    from mxnet_tpu.gluon import rnn

    lstm = rnn.LSTM(64, num_layers=2, layout="TNC", input_size=64)
    lstm.initialize()
    lstm.cast("bfloat16")
    x = mx.np.array(onp.random.RandomState(0).randn(5, 2, 64),
                    dtype="bfloat16")

    carries = []
    scan = jax.lax.scan

    def spy(f, init, xs, **kw):
        carries.append([str(c.dtype) for c in init] + [str(xs.dtype)])
        return scan(f, init, xs, **kw)

    monkeypatch.setattr(jax.lax, "scan", spy)
    out = lstm(x)
    assert str(out.dtype) == "bfloat16"
    assert carries == [["bfloat16"] * 3] * 2     # h, c, x_proj; per layer


def test_bucket_sentence_iter():
    onp.random.seed(2)
    sentences = [list(onp.random.randint(1, 20, onp.random.randint(3, 15)))
                 for _ in range(100)]
    it = BucketSentenceIter(sentences, batch_size=8, buckets=[5, 10, 15])
    seen_keys = set()
    n_batches = 0
    for batch in it:
        n_batches += 1
        seen_keys.add(batch.bucket_key)
        data = batch.data[0].asnumpy()
        label = batch.label[0].asnumpy()
        assert data.shape == (8, batch.bucket_key)
        # label is data shifted left by one
        assert onp.array_equal(label[:, :-1], data[:, 1:])
    assert n_batches > 0
    assert len(seen_keys) > 1  # multiple buckets exercised
    # shapes come from a small fixed set -> bounded jit cache
    assert seen_keys <= {5, 10, 15}


def test_bucket_iter_discards_overlong():
    sentences = [[1, 2, 3], [1] * 50]
    it = BucketSentenceIter(sentences, batch_size=1, buckets=[5])
    assert it.ndiscard == 1


def test_im2rec_roundtrip(tmp_path):
    """Pack a tiny synthetic image tree and read it back via
    ImageRecordDataset."""
    from PIL import Image
    root = tmp_path / "imgs"
    for cls in ["cat", "dog"]:
        (root / cls).mkdir(parents=True)
        for i in range(3):
            arr = onp.random.randint(0, 255, (10, 12, 3), dtype=onp.uint8)
            Image.fromarray(arr).save(root / cls / f"{i}.png")

    prefix = str(tmp_path / "pack")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(repo, "tools", "im2rec.py"),
         prefix, str(root)],
        capture_output=True, text=True, env=env, timeout=300)
    assert out.returncode == 0, out.stderr
    assert os.path.exists(prefix + ".rec") and os.path.exists(prefix + ".idx")

    from mxnet_tpu.gluon.data.vision import ImageRecordDataset
    ds = ImageRecordDataset(prefix + ".rec")
    assert len(ds) == 6
    img, label = ds[0]
    assert img.shape == (10, 12, 3)
    assert label in (0.0, 1.0)
    labels = sorted(ds[i][1] for i in range(6))
    assert labels == [0.0, 0.0, 0.0, 1.0, 1.0, 1.0]
