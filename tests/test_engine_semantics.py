"""Engine-contract tests.

Reference: `tests/python/unittest/test_engine.py` + `test_exc_handling.py`
— the dependency-engine semantics users rely on: in-place mutation
ordering, version tracking, waitall, and tape safety of mutation.  Here
PjRt streams + NDArray rebind-versioning provide the same contracts.
"""
import numpy as onp

import mxnet_tpu as mx
from mxnet_tpu import autograd


def test_mutation_bumps_version():
    a = mx.np.ones(3)
    v0 = a.version
    a += 1
    v1 = a.version
    assert v1 > v0
    a[0] = 5.0
    assert a.version > v1


def test_waitall_and_wait_to_read():
    a = mx.np.ones((64, 64))
    for _ in range(5):
        a = a @ a * 0.01
    a.wait_to_read()      # WaitForVar analogue
    mx.waitall()          # WaitForAll analogue
    assert onp.isfinite(a.asnumpy()).all()


def test_inplace_mutation_under_record_is_safe():
    """The reference engine serializes write-after-read; here the tape
    snapshots by value, so mutating an input AFTER it was used does not
    corrupt recorded history (invoke.py docstring contract)."""
    x = mx.np.array([2.0, 3.0])
    x.attach_grad()
    with autograd.record():
        y = (x * x).sum()   # reads x
        x += 10.0           # mutates x afterwards
    y.backward()
    # gradient reflects the value AT USE TIME (2x), not the mutated one
    assert onp.allclose(x.grad.asnumpy(), [4.0, 6.0])


def test_write_after_read_ordering():
    """a = b + c then b mutated: a must keep the pre-mutation value."""
    b = mx.np.ones(4)
    c = mx.np.ones(4)
    a = b + c
    b += 100.0
    assert onp.allclose(a.asnumpy(), 2.0)


def test_sync_errors_raise_at_call():
    """Shape/dtype misuse raises immediately at dispatch (stricter than
    the reference's throw-at-WaitToRead, never looser)."""
    a = mx.np.ones((2, 3))
    b = mx.np.ones((4, 5))
    try:
        _ = a @ b
        raise AssertionError("expected a shape error")
    except (TypeError, ValueError):
        pass


def test_detach_and_stop_gradient():
    x = mx.np.array([1.0, 2.0])
    x.attach_grad()
    with autograd.record():
        y = x * 3
        z = (y.detach() * x).sum()
    z.backward()
    # d/dx (const * x) = const = 3x values
    assert onp.allclose(x.grad.asnumpy(), [3.0, 6.0])


def test_grad_req_add_accumulates():
    x = mx.np.array([1.0, 1.0])
    x.attach_grad(grad_req="add")
    for _ in range(3):
        with autograd.record():
            y = (x * x).sum()
        y.backward()
    assert onp.allclose(x.grad.asnumpy(), [6.0, 6.0])  # 3 * 2x


def test_engine_debug_flags_stale_read(monkeypatch):
    """MXNET_ENGINE_DEBUG=1 (reference §5.2 versioned-var visibility): a
    leaf mutated in place AFTER being consumed by a recorded op gets a
    stale-read warning at backward — the gradient describes the value at
    record time.

    The env var is read ONCE at import (mxlint env-read-at-trace-time),
    so the test toggles the module flag, not the environment."""
    import warnings

    from mxnet_tpu import autograd
    from mxnet_tpu.ops import invoke as _invoke

    monkeypatch.setattr(_invoke, "_ENGINE_DEBUG", True)
    x = mx.np.array(onp.array([1.0, 2.0], "f"))
    x.attach_grad()
    with autograd.record():
        y = (x * x).sum()
    x += 5.0  # in-place mutation after the tape read x
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        y.backward()
    msgs = [str(w.message) for w in caught]
    assert any("stale read" in m for m in msgs), msgs
    # gradient is w.r.t. the RECORDED value (2x at x=[1,2])
    onp.testing.assert_allclose(x.grad.asnumpy(), [2.0, 4.0])

    # without the flag: no warning (zero overhead on the hot path)
    monkeypatch.setattr(_invoke, "_ENGINE_DEBUG", False)
    x2 = mx.np.array(onp.array([1.0], "f"))
    x2.attach_grad()
    with autograd.record():
        y2 = (x2 * 2).sum()
    x2 += 1.0
    with warnings.catch_warnings(record=True) as caught2:
        warnings.simplefilter("always")
        y2.backward()
    assert not [w for w in caught2 if "stale read" in str(w.message)]
