"""Test harness config.

Asks JAX for the host CPU platform with 8 virtual devices BEFORE jax is
imported anywhere, so multi-chip sharding tests (shard_map over a Mesh)
run without TPU hardware -- explicitly: the servers tests start refuse a
silent host fall-back (util/costmodel.check_device).
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

# tests run uncached, through jax's own switch: a disk cache that warms
# up during the session changes the timing the batching tests lean on,
# and XLA:CPU logs a machine-feature warning on every AOT load. Tests of
# the cache itself switch it back on (test_costplane, test_chip_smoke).
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

import pytest  # noqa: E402


@pytest.fixture
def rng():
    import random

    return random.Random(1234)


@pytest.fixture(autouse=True)
def _isolate_resilience_plane():
    """The chaos plane and the circuit-breaker registry are process-wide
    singletons (by design: one state for /status, /metrics and every
    seam). Between tests they must not leak -- a breaker opened by one
    test's injected failures would shed another test's shard jobs."""
    from tempo_tpu.chaos import plane
    from tempo_tpu.util import breaker

    plane.reset_for_tests()
    breaker.reset_for_tests()
    yield
    plane.reset_for_tests()
    breaker.reset_for_tests()


def pytest_configure(config):
    config.addinivalue_line("markers", "slow: multi-process / long-running e2e tests")
    config.addinivalue_line(
        "markers",
        "fleet: fleet-topology e2e (replication / quorum / rolling restart)")
