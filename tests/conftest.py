"""Test harness: fake 8-device CPU cluster.

Mirrors the reference's CPU/multi-process testing strategy
(realhf/base/testing.py: LocalMultiProcessTest with gloo) the JAX way — a
single process sees 8 virtual CPU devices via
--xla_force_host_platform_device_count, so every sharding/mesh code path is
exercised without TPU hardware.
"""

import os

# Must be set before jax is imported anywhere.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    flags += " --xla_force_host_platform_device_count=8"
os.environ["XLA_FLAGS"] = flags.strip()

# Persistent XLA compilation cache: the suite compiles hundreds of tiny
# CPU programs and recompilation dominates wall-clock on small CI hosts;
# worker subprocesses (apps/worker.py) enable the same directory, so they
# reuse the parent's compiles and repeat runs start warm.
from areal_tpu.base import compilation_cache

compilation_cache.enable()

import numpy as np
import pytest


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: multi-second end-to-end trials, excluded from the tier-1 "
        "`-m 'not slow'` run (scripts/check_async.py covers the async e2e)",
    )


@pytest.fixture(autouse=True)
def _fresh_name_resolve():
    from areal_tpu.base import name_resolve

    name_resolve.set_default(name_resolve.MemoryNameResolveRepository())
    yield
    name_resolve.reset()


@pytest.fixture
def rng():
    return np.random.default_rng(42)
