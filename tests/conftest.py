"""Test harness: run JAX on a virtual 8-device CPU mesh.

Multi-chip hardware isn't available in CI; shardings are validated the way the
reference validates multi-node logic with in-process fakes (SURVEY.md §4) — here via
XLA's host-platform device partitioning. Must run before jax is imported anywhere.
"""

import os

# Tests ask for CPU explicitly, whatever the caller exported: this process
# through jax.config below, daemon subprocesses through the inherited env
# (the harness hands them no platform of its own).
os.environ["JAX_PLATFORMS"] = "cpu"
# Arm the lock-order sanitizer for the WHOLE suite (subprocess daemons
# inherit it via the harness env): every MiniCluster/ProcCluster e2e then
# doubles as a race/deadlock probe. utils/locks.py checks this at lock
# construction, so it must be set before any chubaofs_tpu import below.
# Export CFS_LOCK_SANITIZER=0 to measure un-instrumented timings.
os.environ.setdefault("CFS_LOCK_SANITIZER", "1")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture
def rng():
    return np.random.default_rng(0)


# the one shared bit-rot injector now lives with the chaos subsystem
# (chaos/inject.py); re-exported so older suites keep their import path
from chubaofs_tpu.chaos.inject import corrupt_shard_on_disk  # noqa: E402, F401


@pytest.fixture(autouse=True)
def _chaos_clean():
    """No test may leak armed failpoints into the next one."""
    from chubaofs_tpu import chaos

    yield
    chaos.reset()
