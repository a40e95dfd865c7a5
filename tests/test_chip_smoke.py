"""`chip_smoke.py` is the bring-up check a builder sends to the chip; what
can be rehearsed of it on the CPU is here, so a call is not spent on a typo."""
import math
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_import_touches_no_device():
    """Importing the script loads neither jax nor the package: a parent
    that imports it (a test, a launcher) still leaves the chip free."""
    code = ("import sys, chip_smoke; "
            "assert 'jax' not in sys.modules and 'mxnet_tpu' not in sys.modules")
    r = subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr


def test_bert_flash_builder_runs_a_finite_step():
    """The BERT phase's model is `chipbench`'s bert_base held to the flash
    kernel (interpreted here): two layers, a small vocabulary, T=64, one
    ragged batch."""
    import chip_smoke

    step, batch = chip_smoke.bert_flash_step(0, 2, 64, num_hidden_layers=2,
                                             vocab_size=1024)
    assert [a.shape for a in batch] == [(2, 64)] * 4
    lengths = batch[3].asnumpy().sum(axis=1)
    assert ((32 <= lengths) & (lengths <= 64)).all() and lengths.min() < 64
    assert math.isfinite(float(step(*batch, batch_size=2).asnumpy()))
