"""Test harness config: force an 8-device virtual CPU platform BEFORE jax
import so sharded tests (shard_map/pjit over a Mesh) run hermetically without
TPU hardware. Mirrors the reference's strategy of scale-testing the server
tier on one box (partha/test_multi_partha.sh — N agents, one machine)."""

import os

from gyeeta_tpu.utils import xlacache

# the suite needs 8 devices, which only the CPU backend can fake; an
# exported JAX_PLATFORMS still means what jax says it means
os.environ.setdefault("JAX_PLATFORMS", "cpu")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

# persistent compilation cache, shared by every worker and every run
# (XLA compiles are the dominant test cost): the one placement rule of
# utils/xlacache.py, before jax reads the environment at import
xlacache.configure()

import jax  # noqa: E402,F401
import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture
def rng():
    return np.random.default_rng(12345)


# CI tiering: `ci.sh fast` and the driver's tier-1 command run
# everything without the `slow` marker — the two runtimes' own modules
# (mesh programs, socket e2e, full-runtime flows) included, since they
# guard every PR that edits the dispatch path. Slow by module: the
# 131k-row scale test; a few long tests elsewhere are marked by name.
_SLOW_MODULES = {"test_scale"}


def pytest_collection_modifyitems(items):
    for item in items:
        if item.module.__name__ in _SLOW_MODULES:
            item.add_marker(pytest.mark.slow)
