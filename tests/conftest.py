"""Test harness: force an 8-device virtual CPU mesh so sharding/collective paths are
exercised without TPU hardware (ref test strategy: akka-multi-node-testkit runs multi-node
behavior in one process — coordinator/src/multi-jvm/).

The tests run on the CPU whatever the machine has: the platform is pinned in
the jax *config* before the first backend initialization, so an inherited
JAX_PLATFORMS cannot send them to a chip. The persistent compilation cache is
off here: servers started by tests would otherwise place it in the checkout
(utils/compilecache.py) and six xdist workers would fill it with CPU programs.
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

# runtime lock-order assertions (diagnostics.LOCK_ORDER, the statically
# derived order filolint checks): every tier-1 run doubles as a deadlock
# canary — must be set before filodb_tpu.utils.diagnostics first imports
os.environ.setdefault("FILODB_LOCK_DEBUG", "1")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", True)
jax.config.update("jax_enable_compilation_cache", False)

import numpy as np  # noqa: E402
import pytest  # noqa: E402

assert jax.devices()[0].platform == "cpu", "tests must run on the virtual CPU mesh"
assert len(jax.devices()) == 8, "expected an 8-device virtual CPU mesh"


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(42)


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: multi-process / wall-clock-heavy tests")
