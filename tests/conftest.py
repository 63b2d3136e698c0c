"""Test configuration.

Forces JAX onto a virtual 8-device CPU platform (the reference's analogue is
its fake multi-node Cluster fixture, SURVEY.md §4) so mesh/sharding paths are
exercised without TPU hardware.  Runs before anything imports jax, and the
worker processes inherit the environment.
"""

import os

# A hard overwrite, not setdefault: a machine with a chip sets JAX_PLATFORMS
# to it, and the tests must not take the chip.
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

from ray_tpu.util import compile_cache  # noqa: E402

# Persistent compilation cache: the heavyweight jitted programs (e.g. the
# PPO scan-of-scans update) compile once per machine instead of once per
# pytest run.  Harmless for correctness — keyed on HLO + flags.
compile_cache.enable()

import pytest  # noqa: E402

# Hang forensics: if any test wedges the process for 10 minutes, dump every
# thread's stack to a file (pytest's capture hides stderr, so a file it is).
import faulthandler  # noqa: E402

_hang_dump = open("/tmp/pytest_hang_dump.txt", "w")
faulthandler.dump_traceback_later(600, repeat=True, file=_hang_dump)


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: heavyweight tests excluded from the tier-1 run (-m 'not slow')")


@pytest.fixture(scope="session")
def ray_cluster():
    import ray_tpu

    # The ONE canonical cluster config for the whole pytest session: module
    # fixtures depend on this fixture instead of calling init themselves,
    # so no selection/ordering of test modules can create the cluster with
    # a different config.  CPU is virtualized (the CI host has 1 real
    # core); 8 covers the serve tests' controller+proxy+3 replicas.
    node = ray_tpu.init(
        min_workers=2,
        max_workers=8,
        object_store_memory=1 << 28,
        resources={"CPU": 8.0},
        ignore_reinit_error=True,
    )
    yield node
    ray_tpu.shutdown()


@pytest.fixture
def shutdown_only():
    import ray_tpu

    yield
    ray_tpu.shutdown()
