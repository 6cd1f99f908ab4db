# NOTE: no XLA_FLAGS here on purpose — tests run on the single real CPU
# device; multi-device behaviour is covered by subprocess tests
# (test_distributed.py) which set --xla_force_host_platform_device_count
# in the child process only.
import os

# the suite runs on the CPU wherever it is started: on a machine with a TPU
# the pytest process must not take the chip, and the subprocess tests
# inherit the setting
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import pytest  # noqa: E402

from repro.launch.mesh import make_test_mesh  # noqa: E402


@pytest.fixture(scope="session")
def mesh1():
    """1x1 mesh: exercises the full shard_map/collective code path on one
    device (all_to_all over a size-1 axis is identity)."""
    return make_test_mesh(1, 1)


AXES = ("data", "model")


@pytest.fixture(scope="session")
def axes():
    return AXES
