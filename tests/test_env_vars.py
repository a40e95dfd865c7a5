"""Environment-variable configuration surface (VERDICT r1 missing #9).

Reference: the documented MXNET_* env vars
(`docs/static_site/src/pages/api/faq/env_var.md`); the honored subset and
semantics live in `mxnet_tpu/env.py`.
"""
import os
import subprocess
import sys
import textwrap

import mxnet_tpu as mx

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code, **env_extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.update(env_extra)
    return subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=180)


def test_mxnet_seed_reproducible():
    code = """
        import mxnet_tpu as mx
        print(float(mx.np.random.uniform(0, 1, size=()).asnumpy()))
    """
    a = _run(code, MXNET_SEED="123")
    b = _run(code, MXNET_SEED="123")
    c = _run(code, MXNET_SEED="456")
    assert a.returncode == 0, a.stderr
    assert a.stdout == b.stdout
    assert a.stdout != c.stdout


def test_naive_engine_surfaces_errors_at_the_op():
    """NaiveEngine blocks per op, so the async error raises at the
    faulting call, not at a later wait (reference debug-engine use)."""
    code = """
        import mxnet_tpu as mx
        import mxnet_tpu.env as env
        assert env.is_naive_engine()
        ok = True
        print("naive-ok")
    """
    r = _run(code, MXNET_ENGINE_TYPE="NaiveEngine")
    assert r.returncode == 0, r.stderr
    assert "naive-ok" in r.stdout


def test_bulk_and_worker_threads_env():
    code = """
        import mxnet_tpu as mx
        from mxnet_tpu import engine, env
        assert engine._bulk_size == 31, engine._bulk_size
        assert env.cpu_worker_nthreads() == 3
        print("env-ok")
    """
    r = _run(code, MXNET_EXEC_BULK_EXEC_TRAIN="31",
             MXNET_CPU_WORKER_NTHREADS="3")
    assert r.returncode == 0, r.stderr
    assert "env-ok" in r.stdout


def test_kvstore_bucketing_env_optout():
    """MXNET_KVSTORE_BUCKETING=0 disables gradient bucketing process-wide:
    the Trainer falls back to one collective per parameter."""
    code = """
        import numpy as onp
        import mxnet_tpu as mx
        from mxnet_tpu import autograd, telemetry
        from mxnet_tpu.gluon.utils import split_and_load
        from mxnet_tpu.kvstore import bucketing
        assert not bucketing.bucketing_enabled()
        ctxs = [mx.cpu(i) for i in range(2)]
        net = mx.gluon.nn.Dense(4, in_units=3)
        net.initialize(ctx=ctxs)
        tr = mx.gluon.Trainer(net.collect_params(), "sgd",
                              {"learning_rate": 0.1}, kvstore="tpu_ici")
        def step():
            xs = split_and_load(mx.np.array(
                onp.random.randn(4, 3).astype(onp.float32)), ctxs)
            with autograd.record():
                ls = [(net(x) ** 2).mean() for x in xs]
            autograd.backward(ls)
            tr.step(4)
        step()
        reg = telemetry.default_registry()
        name = "mxtpu_kvstore_collective_launches_total"
        before = reg.get_sample_value(name) or 0.0
        step()
        delta = (reg.get_sample_value(name) or 0.0) - before
        assert delta == 2, delta  # one collective per param: weight, bias
        print("bucketing-off-ok")
    """
    r = _run(code, MXNET_KVSTORE_BUCKETING="0",
             XLA_FLAGS="--xla_force_host_platform_device_count=8")
    assert r.returncode == 0, r.stderr
    assert "bucketing-off-ok" in r.stdout


def test_kvstore_bucket_bytes_env():
    """MXNET_KVSTORE_BUCKET_BYTES caps bucket payloads (read when the
    bucketer is created)."""
    code = """
        import numpy as onp
        import mxnet_tpu as mx
        from mxnet_tpu.kvstore import bucketing
        assert bucketing.bucketing_enabled()
        assert bucketing.bucket_bytes() == 2048
        b = bucketing.GradBucketer()
        assert b.bucket_bytes == 2048
        pairs = [(k, [mx.np.array(onp.full(256, 1.0, onp.float32),
                                  ctx=mx.cpu(c)) for c in range(2)])
                 for k in range(8)]   # 1 KB tensors, 2 KB cap -> 4 buckets
        b.pushpull(pairs)
        assert b.last_num_buckets == 4, b.last_num_buckets
        print("bucket-bytes-ok")
    """
    r = _run(code, MXNET_KVSTORE_BUCKET_BYTES="2048",
             XLA_FLAGS="--xla_force_host_platform_device_count=8")
    assert r.returncode == 0, r.stderr
    assert "bucket-bytes-ok" in r.stdout


def test_describe_lists_honored_vars():
    table = mx.env.describe()
    names = [n for n, _v, _h in table]
    assert "MXNET_SEED" in names and "MXNET_ENGINE_TYPE" in names
    assert all(h for _n, _v, h in table)


def test_env_inventory_matches_describe_exactly():
    """ISSUE 5: the env-var surface can never drift again.  mxlint's
    AST inventory of every MXNET_* access across mxnet_tpu/, tools/,
    and benchmark/ must equal describe()'s documented table, modulo the
    two declared accepted-no-op knobs (documented for reference parity,
    intentionally never read).  A new knob read without documentation
    fails here AND fails `python -m tools.mxlint` in CI; a documented
    knob whose last read is deleted fails here until the table (or
    DECLARED_NOOPS) is updated."""
    from tools.mxlint.rules.env_doc import (DECLARED_NOOPS,
                                            discovered_env_vars,
                                            documented_env_vars)

    documented = documented_env_vars()
    discovered = set(discovered_env_vars())
    undocumented = discovered - documented
    assert not undocumented, \
        f"MXNET_* vars read in code but missing from env.describe(): " \
        f"{sorted(undocumented)}"
    never_read = documented - discovered
    assert never_read == set(DECLARED_NOOPS), \
        f"documented vars with no read site (and not declared no-ops): " \
        f"{sorted(never_read - set(DECLARED_NOOPS))} / stale no-op " \
        f"declarations: {sorted(set(DECLARED_NOOPS) - never_read)}"
    # the AST view agrees with the live function
    assert documented == {n for n, _v, _h in mx.env.describe()}


def test_engine_debug_env_read_once_at_import():
    """MXNET_ENGINE_DEBUG is read once at import (it is consulted per
    recorded op on the tape hot path), so setting it pre-import works
    and post-import changes are inert."""
    code = """
        import mxnet_tpu as mx
        from mxnet_tpu.ops import invoke
        assert invoke._ENGINE_DEBUG is True
        import os
        os.environ["MXNET_ENGINE_DEBUG"] = "0"   # post-import: inert
        assert invoke._engine_debug() is True
        print("engine-debug-ok")
    """
    r = _run(code, MXNET_ENGINE_DEBUG="1")
    assert r.returncode == 0, r.stderr
    assert "engine-debug-ok" in r.stdout


def test_dropout_rng_env_var_is_inert_and_impl_overrides(monkeypatch):
    """`MXNET_DROPOUT_RNG` is gone: dropout's bit generator is the
    constant `rbg` (it had one value in use).  Setting the variable has
    no effect; the programmatic `impl=` override still works."""
    import jax
    import numpy as onp

    from mxnet_tpu.ops import nn as _nn

    key = jax.random.key(0)
    before = jax.random.key_data(_nn._dropout_key(key))
    monkeypatch.setenv("MXNET_DROPOUT_RNG", "threefry")
    after = jax.random.key_data(_nn._dropout_key(key))
    # the variable is read nowhere (rbg re-wrap in both)
    assert (onp.asarray(before) == onp.asarray(after)).all()
    assert _nn._DROPOUT_RNG_IMPL == "rbg"  # the constant
    # explicit impl override bypasses the constant
    tf = _nn._dropout_key(key, impl="threefry")
    assert jax.random.key_data(tf).size == 2       # untouched threefry key
    assert jax.random.key_data(_nn._dropout_key(key)).size == 4  # rbg wrap
