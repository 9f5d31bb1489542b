"""Test harness config.

JAX tests run on a virtual 8-device CPU mesh (the analogue of the
reference's fake-GPU / fake-multinode strategy, SURVEY.md §4): XLA is
forced to expose 8 host devices so every sharding/collective path compiles
and executes without TPU hardware. Must be set before jax imports.
"""

import os

os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=8")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("JAX_ENABLE_X64", "0")

# Tests run on the CPU whatever the environment says.
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402


def pytest_configure(config):
    # No pytest.ini in this repo: markers register here so -m filters
    # ("not slow" in the tier-1 command) and --strict-markers both work.
    config.addinivalue_line(
        "markers", "chaos: fault-injection recovery test (failpoints)")
    config.addinivalue_line(
        "markers", "slow: multi-second test, excluded from tier-1")


@pytest.fixture(autouse=True)
def _failpoint_leak_guard():
    """No chaos test may leak armed failpoints into its neighbors: the
    registry (and the inheritance env var) must be empty at test exit."""
    from raytpu.util import failpoints

    yield
    leaked = failpoints.active()
    env_leak = os.environ.get(failpoints.ENV_VAR)
    if leaked or env_leak:
        failpoints.clear()  # don't cascade the failure into later tests
        pytest.fail(f"failpoints leaked past test exit: "
                    f"registry={leaked}, {failpoints.ENV_VAR}={env_leak!r}")


@pytest.fixture
def raytpu_local():
    """A fresh single-process fabric per test (reference fixture analogue:
    ``ray_start_regular``, ``python/ray/tests/conftest.py:412``)."""
    import raytpu

    raytpu.shutdown()
    raytpu.init(num_cpus=4)
    yield raytpu
    raytpu.shutdown()


@pytest.fixture
def raytpu_local_tpu():
    """Fabric with 8 fake TPU chips for topology-aware scheduling tests."""
    import raytpu

    raytpu.shutdown()
    raytpu.init(num_cpus=4, num_tpus=8)
    yield raytpu
    raytpu.shutdown()
