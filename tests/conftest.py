"""Test configuration.

Mirrors the reference's conftest strategy (`conftest.py:61-119`): seeded RNG
per test with the seed logged for repro, and a drain between tests to
localize async failures.  Tests run on a virtual 8-device CPU mesh so
multi-chip sharding paths execute without TPU hardware (the driver
separately dry-runs the multichip path; see `__graft_entry__.py`).
"""
import os

# Both must land before the first backend is created (they do: none
# exists yet at conftest import time), and subprocesses inherit them.
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
assert jax.devices()[0].platform == "cpu" and len(jax.devices()) >= 8, (
    "tests must run on the virtual 8-device CPU mesh, got "
    f"{jax.devices()}")

import numpy as onp  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _seed_rng(request):
    seed = onp.random.randint(0, 2 ** 31)
    module_seed = int(os.environ.get("MXNET_TPU_TEST_SEED", seed))
    # log the seed so a flaky failure is reproducible with
    # MXNET_TPU_TEST_SEED=<seed> (reference conftest.py:75-119 prints seeds)
    print(f"[seed {module_seed}]", end=" ", flush=True)
    onp.random.seed(module_seed)
    import mxnet_tpu as mx
    mx.random.seed(module_seed)
    yield
    # drain async work so failures localize to the test that caused them
    # (reference: conftest.py waitall between modules)
    mx.waitall()


@pytest.fixture(autouse=True, scope="module")
def _routed_layers_do_not_outlive_their_module():
    """`parallel.moe.expert_loads()` reads every LIVE `RoutedExperts` layer,
    and the benchmark's FLOP counts read it (`chipbench/layer_work.py`).  A
    model that a test module trained can stay alive past the module (jit and
    tape caches hold it), and its toy counters then stand in a later
    module's counts: after each module, collect what can be collected and
    zero the counters of what is left (zero rows read as "no counter")."""
    yield
    import gc
    import sys
    gc.collect()
    moe = sys.modules.get("mxnet_tpu.parallel.moe")
    for layer in list(moe._ROUTED_LAYERS) if moe else ():
        load = layer.expert_load
        if load._data is not None:
            load.data()._rebind(jax.numpy.zeros(load.shape, "int32"))


@pytest.fixture
def rng():
    """Per-test numpy Generator seeded by the autouse seed fixture."""
    return onp.random.default_rng(onp.random.randint(0, 2 ** 31))


def pytest_configure(config):
    config.addinivalue_line("markers", "seed: fixed-seed test")
    config.addinivalue_line("markers", "serial: serial-only test")
    config.addinivalue_line(
        "markers", "slow: excluded from the tier-1 'not slow' run")
