"""Autotune cache, choke point, driver gate, and bit-parity contracts.

The load-bearing promises from docs/AUTOTUNE.md:

* the cache is a committed, diffable JSON artifact with stable keys
  (round-trips byte-identically through save/load);
* a miss — unknown key, missing file, toolchain-fingerprint mismatch —
  falls back to the documented static default with ONE AutotuneMiss
  warning, never a crash and never an in-process sweep;
* the CI gate (tools/autotune) FAILS on stale entries instead of
  silently ignoring them;
* switching a kernel between its default and tuned params never moves
  a bit: the q-block split of flash attention and the (tm, tn) tiling
  of the s2d stem matmul are numerics-free choices, fwd AND bwd.
"""
import json
import warnings

import numpy as onp
import pytest

from mxnet_tpu import tune
from mxnet_tpu.tune.cache import empty_cache

pytestmark = pytest.mark.serial  # shared tune._memo + env vars


@pytest.fixture(autouse=True)
def _fresh_memo(monkeypatch):
    """Every test sees an un-memoized choke point and controls the
    cache path explicitly (never the committed repo cache)."""
    monkeypatch.delenv("MXNET_AUTOTUNE", raising=False)
    tune.invalidate()
    yield
    tune.invalidate()


def _write_cache(path, entries, fingerprint=None):
    doc = empty_cache()
    if fingerprint is not None:
        doc["fingerprint"] = fingerprint
    doc["entries"] = entries
    tune.save_cache(doc, str(path))
    return str(path)


# --------------------------------------------------------------------------
# cache document: schema, keys, round-trip
# --------------------------------------------------------------------------
def test_cache_roundtrip_byte_stable(tmp_path):
    sig = tune.signature("bfloat16", device="tpu-v5e", b=8, h=8, t=4096,
                         d=64)
    key = tune.make_key("flash_attention", sig)
    assert key == "flash_attention|b8.d64.h8.t4096|bf16|tpu-v5e"
    assert tune.split_key(key) == ("flash_attention", "b8.d64.h8.t4096",
                                   "bf16", "tpu-v5e")
    p = tmp_path / "cache.json"
    _write_cache(p, {key: {"params": {"block_q": 512, "block_k": 1024},
                           "mode": "model", "speedup_vs_default": 1.0}})
    doc = tune.load_cache(str(p))
    assert doc["schema"] == tune.SCHEMA
    assert doc["entries"][key]["params"] == {"block_q": 512,
                                             "block_k": 1024}
    # canonical formatting: a save of the loaded doc reproduces the file
    first = p.read_bytes()
    tune.save_cache(doc, str(p))
    assert p.read_bytes() == first


def test_signature_buckets_to_pow2():
    # t=1000 and t=1024 share a bucket (and thus a cache entry)
    a = tune.signature("bfloat16", device="tpu-v5e", b=32, t=1000, h=650)
    b = tune.signature("bfloat16", device="tpu-v5e", b=32, t=1024, h=650)
    assert a == b == "b32.h1024.t1024|bf16|tpu-v5e"


def test_load_rejects_malformed(tmp_path):
    p = tmp_path / "bad.json"
    p.write_text(json.dumps({"schema": "something-else", "entries": {}}))
    with pytest.raises(ValueError):
        tune.load_cache(str(p))
    p.write_text(json.dumps({"schema": tune.SCHEMA,
                             "fingerprint": tune.fingerprint(),
                             "entries": {"only|three|parts":
                                         {"params": {}}}}))
    with pytest.raises(ValueError):
        tune.load_cache(str(p))


# --------------------------------------------------------------------------
# the choke point: miss policy
# --------------------------------------------------------------------------
def test_miss_unknown_key_warns_once_then_silent(tmp_path, monkeypatch):
    sig = tune.signature("bfloat16", device="tpu-v5e", b=8, t=128)
    path = _write_cache(tmp_path / "c.json", {})
    monkeypatch.setenv("MXNET_AUTOTUNE_CACHE", path)
    tune.invalidate()
    with pytest.warns(tune.AutotuneMiss, match="no entry"):
        got = tune.best("flash_attention", sig, {"block_q": 512})
    assert got == {"block_q": 512}
    with warnings.catch_warnings():
        warnings.simplefilter("error")  # second lookup must NOT warn
        assert tune.best("flash_attention", sig,
                         {"block_q": 512}) == {"block_q": 512}


def test_fingerprint_mismatch_is_default_plus_warning(tmp_path,
                                                      monkeypatch):
    sig = tune.signature("bfloat16", device="tpu-v5e", b=8, t=128)
    key = tune.make_key("flash_attention", sig)
    path = _write_cache(
        tmp_path / "c.json",
        {key: {"params": {"block_q": 64}, "mode": "time"}},
        fingerprint={"schema": tune.SCHEMA, "jax": "0.0.stale"})
    monkeypatch.setenv("MXNET_AUTOTUNE_CACHE", path)
    tune.invalidate()
    # never a crash, never the stale entry — the default, plus ONE warning
    with pytest.warns(tune.AutotuneMiss, match="fingerprint|toolchain"):
        got = tune.best("flash_attention", sig, {"block_q": 512})
    assert got == {"block_q": 512}
    assert tune.lookup("flash_attention", sig) is None
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        tune.best("flash_attention", sig, {"block_q": 512})


def test_missing_cache_file_warns_and_defaults(tmp_path, monkeypatch):
    monkeypatch.setenv("MXNET_AUTOTUNE_CACHE",
                       str(tmp_path / "nowhere.json"))
    tune.invalidate()
    with pytest.warns(tune.AutotuneMiss, match="not found"):
        got = tune.best("stem_s2d", "b8.c64.h64.w64|bf16|tpu-v5e",
                        {"tm": 512, "tn": 128})
    assert got == {"tm": 512, "tn": 128}


def test_autotune_disabled_is_silent(tmp_path, monkeypatch):
    monkeypatch.setenv("MXNET_AUTOTUNE", "0")
    monkeypatch.setenv("MXNET_AUTOTUNE_CACHE",
                       str(tmp_path / "nowhere.json"))
    tune.invalidate()
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        got = tune.best("stem_s2d", "b8.c64.h64.w64|bf16|tpu-v5e",
                        {"tm": 512, "tn": 128})
    assert got == {"tm": 512, "tn": 128}


def test_hit_returns_committed_params(tmp_path, monkeypatch):
    sig = tune.signature("bfloat16", device="tpu-v5e", b=8, t=128)
    key = tune.make_key("flash_attention", sig)
    path = _write_cache(tmp_path / "c.json",
                        {key: {"params": {"block_q": 64, "block_k": 128},
                               "mode": "time"}})
    monkeypatch.setenv("MXNET_AUTOTUNE_CACHE", path)
    tune.invalidate()
    with warnings.catch_warnings():
        warnings.simplefilter("error")  # a hit is silent
        got = tune.best("flash_attention", sig, {"block_q": 512,
                                                 "block_k": 1024})
    assert got == {"block_q": 64, "block_k": 128}
    got["block_q"] = 7  # caller mutation must not poison the memo
    assert tune.best("flash_attention", sig, {})["block_q"] == 64


# --------------------------------------------------------------------------
# the driver gate
# --------------------------------------------------------------------------
def test_verify_stale_entry_fails(tmp_path, monkeypatch):
    from tools.autotune import verify_cache
    path = _write_cache(
        tmp_path / "c.json",
        {"no_such_kernel|b8.t128|bf16|tpu-v5e":
         {"params": {"x": 1}, "mode": "time"}})
    findings, _ = verify_cache(path=path, kernels_filter=["no_such_kernel"])
    assert [f["rule"] for f in findings] == ["stale-entry"]

    from tools.autotune.driver import main
    monkeypatch.setattr("sys.argv", ["autotune"])
    assert main(["--cache", path, "--kernel", "flash_attention"]) == 1


def test_verify_params_not_in_grid_is_stale(tmp_path):
    from tools.autotune import verify_cache
    spec = tune.get("flash_attention")
    sig = spec.signatures()[0]
    key = tune.make_key("flash_attention", sig)
    path = _write_cache(
        tmp_path / "c.json",
        {key: {"params": {"block_q": 96, "block_k": 96}, "mode": "time"}})
    findings, _ = verify_cache(path=path,
                               kernels_filter=["flash_attention"])
    rules = {f["rule"] for f in findings}
    assert "stale-entry" in rules


def test_verify_fingerprint_mismatch_fails(tmp_path):
    from tools.autotune import verify_cache
    path = _write_cache(tmp_path / "c.json", {},
                        fingerprint={"schema": tune.SCHEMA,
                                     "jax": "0.0.stale"})
    findings, _ = verify_cache(path=path, kernels_filter=["stem_s2d"])
    assert "fingerprint" in {f["rule"] for f in findings}


@pytest.mark.slow
def test_committed_cache_verifies_clean():
    """The repo's own tools/autotune_cache.json passes the full gate —
    coverage, no stale entries, model winners re-derived bit-for-bit."""
    from tools.autotune import verify_cache
    findings, info = verify_cache()
    assert findings == [], findings
    assert info["entries"] >= 4


# --------------------------------------------------------------------------
# _pick_block regressions (satellite: the old floor-128 fallback)
# --------------------------------------------------------------------------
def test_pick_block_384():
    from mxnet_tpu.ops.pallas_kernels import _pick_block
    # within budget, whole T is one block; over budget, 384 = 2^7 * 3
    # steps down to its largest pow2 divisor <= want, never up
    assert _pick_block(384, 512) == 384
    assert _pick_block(384, 256) == 128
    assert _pick_block(384, 64) == 64


def test_pick_block_1000_small_divisor_not_whole_t():
    from mxnet_tpu.ops.pallas_kernels import _pick_block
    # 1000 = 8 * 125: no pow2 divisor >= 128 exists.  The old floor-128
    # fallback returned the whole T — a single-block kernel whose (T, T)
    # f32 score tile blows VMEM at large T.  The fix walks down to 8.
    assert _pick_block(1000, 512) == 8
    assert 1000 % _pick_block(1000, 512) == 0
    # odd T genuinely has no pow2 divisor: degenerate single block
    assert _pick_block(999, 512) == 999


# --------------------------------------------------------------------------
# bit-parity: tuned params never move a bit
# --------------------------------------------------------------------------
def _flash_qkv(t=256, b=1, h=2, d=16):
    rng = onp.random.RandomState(3)
    return [rng.randn(b, h, t, d).astype(onp.float32) for _ in range(3)]


def test_flash_tuned_vs_default_bit_parity_fwd_bwd(tmp_path, monkeypatch):
    """A cached block_q winner is bitwise-identical to the static
    default in the forward output and dq (the q split never changes
    their accumulation order; block_k is pinned because the k split
    reassociates the softmax accumulation).  dk/dv DO accumulate
    across q-blocks — there a block_q change reorders the f32 sums,
    so the contract is allclose, not bit equality (the committed flash
    winner equals the default, so shipped dispatch is bit-stable
    everywhere anyway)."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops.pallas_kernels import flash_attention

    q, k, v = (jnp.asarray(x) for x in _flash_qkv())
    sig = tune.signature(q.dtype, b=1, h=2, t=256, d=16)
    key = tune.make_key("flash_attention", sig)
    path = _write_cache(tmp_path / "c.json",
                        {key: {"params": {"block_q": 64, "block_k": 64},
                               "mode": "time"}})
    monkeypatch.setenv("MXNET_AUTOTUNE_CACHE", path)
    tune.invalidate()

    def run(fn):
        out = fn(q, k, v)

        def f(q, k, v):
            return fn(q, k, v).astype(jnp.float32).sum()
        _, grads = jax.value_and_grad(f, argnums=(0, 1, 2))(q, k, v)
        return out, grads

    out_t, (gq_t, gk_t, gv_t) = run(
        lambda q, k, v: flash_attention(q, k, v))           # cache: bq=64
    out_d, (gq_d, gk_d, gv_d) = run(
        lambda q, k, v: flash_attention(q, k, v,
                                        block_q=128, block_k=64))
    assert onp.array_equal(onp.asarray(out_t), onp.asarray(out_d))
    assert onp.array_equal(onp.asarray(gq_t), onp.asarray(gq_d))
    for ga, gb in ((gk_t, gk_d), (gv_t, gv_d)):
        onp.testing.assert_allclose(onp.asarray(ga), onp.asarray(gb),
                                    rtol=1e-5, atol=1e-6)


def test_stem_tuned_vs_default_bit_parity_fwd_bwd():
    """Every (tm, tn) stem tile choice is bit-identical fwd and bwd:
    K is never split, and the backward is tile-independent XLA dots."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops.stem import (fold_stem_kernel, space_to_depth2,
                                    stem_conv_pallas)

    rng = onp.random.RandomState(5)
    x = jnp.asarray(rng.randn(2, 3, 32, 32).astype(onp.float32))
    w7 = jnp.asarray(rng.randn(16, 3, 7, 7).astype(onp.float32))
    xs = space_to_depth2(x)
    wf = fold_stem_kernel(w7)

    def loss(tm, tn):
        def f(xs, wf):
            return stem_conv_pallas(xs, wf, tm=tm, tn=tn).astype(
                jnp.float32).sum()
        return jax.value_and_grad(f, argnums=(0, 1))

    val_a, grads_a = loss(512, 128)(xs, wf)     # static default
    val_b, grads_b = loss(64, 8)(xs, wf)        # a very different tiling
    assert onp.array_equal(onp.asarray(val_a), onp.asarray(val_b))
    for ga, gb in zip(grads_a, grads_b):
        assert onp.array_equal(onp.asarray(ga), onp.asarray(gb))


def test_lstm_cast_bf16_both_layers_sign_bf16():
    """`_RNNLayer.cast` must retarget self._dtype (reference behavior):
    otherwise begin_state() emits float32 initial states, the scan carry
    promotes every gate op, layer >= 1 of a bf16 model silently computes
    in f32 — and the lstm_cell autotune lookup misses on dtype."""
    import mxnet_tpu as mx
    from mxnet_tpu import tune
    from mxnet_tpu.gluon import rnn

    lstm = rnn.LSTM(64, num_layers=2, layout="TNC", input_size=64)
    lstm.initialize()
    lstm.cast("bfloat16")
    x = mx.np.array(onp.random.RandomState(0).randn(5, 2, 64),
                    dtype="bfloat16")

    seen = []
    orig = tune.best

    def spy(kernel, sig, default):
        seen.append((kernel, sig))
        return orig(kernel, sig, default)

    tune.best = spy
    try:
        out = lstm(x)
    finally:
        tune.best = orig
    assert str(out.dtype) == "bfloat16"
    assert len(seen) == 2                     # one consult per layer
    for kernel, sig in seen:
        assert kernel == "lstm_cell"
        assert "|bf16|" in sig, sig           # layer 1 used to sign f32
