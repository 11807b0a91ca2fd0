"""Test configuration.

Forces JAX onto a virtual 8-device CPU mesh BEFORE jax import, per the
reference's pattern of simulating a cluster with local processes
(SURVEY §4.1 — tools/launch.py local tracker); here virtual XLA host devices
play the role of the N processes.  The chip is reached only through
``chip_smoke.py`` / ``bench.py`` / ``MXNET_TEST_DEVICE=tpu`` on a machine
that has one.
"""
import os

# Force CPU (overriding any ambient JAX_PLATFORMS) unless the user explicitly
# opts into device testing with MXNET_TEST_DEVICE=tpu.
if not os.environ.get("MXNET_TEST_DEVICE", "").startswith(("tpu", "gpu")):
    os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

import numpy as np
import pytest


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: long-running tests excluded from tier-1 (-m 'not slow') and "
        "the unit CI tier; run explicitly with -m slow")


@pytest.fixture(autouse=True)
def _seed_all(request):
    """Per-test deterministic seeding (reference tests/python/unittest/common.py:97
    @with_seed).  Seed is derived from the test name; printed on failure via -v."""
    import mxnet_tpu as mx

    import zlib

    # stable across processes (str hash() is PYTHONHASHSEED-randomized)
    seed = zlib.crc32(request.node.nodeid.encode()) % (2**31)
    seed = int(os.environ.get("MXNET_TEST_SEED", seed))
    np.random.seed(seed)
    mx.random.seed(seed)
    yield


def load_example_module(name, path):
    """Load an example file under a UNIQUE sys.modules name (several example
    dirs ship a ``train.py``; a bare ``import train`` resolves to whichever
    one another test cached first — order-dependent failures).  Cached by
    name so repeated loads don't re-execute top-level work.  The load itself
    is ``mxnet_tpu.test_utils.load_module_by_path`` (the one shared
    implementation, which also owns the failed-exec cleanup)."""
    import sys

    if name in sys.modules:
        return sys.modules[name]
    from mxnet_tpu.test_utils import load_module_by_path

    return load_module_by_path(path, name)
