"""Test fixtures.  NOTE: no global XLA_FLAGS here — tests must see ONE CPU
device by default; multi-device tests spawn subprocesses with their own
flags (CI additionally exports XLA_FLAGS=--xla_force_host_platform_
device_count=8 so the ``multidevice`` tests run emulated).
"""

import numpy as np
import pytest

from helpers.routes import pallas_route  # noqa: F401  (shared fixture)


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "multidevice: needs jax.device_count() >= 2 (CI emulates 8 via "
        "XLA_FLAGS=--xla_force_host_platform_device_count=8)")
    config.addinivalue_line(
        "markers",
        "slow: long-running stress tests; excluded from the tier-1 run "
        "(pytest -m 'not slow') and run in the bench-smoke CI job")


def pytest_collection_modifyitems(config, items):
    marked = [it for it in items if it.get_closest_marker("multidevice")]
    if not marked:
        return
    import jax
    if jax.device_count() >= 2:
        return
    skip = pytest.mark.skip(
        reason="needs >= 2 jax devices; set "
               "XLA_FLAGS=--xla_force_host_platform_device_count=8")
    for it in marked:
        it.add_marker(skip)


@pytest.fixture
def rng():
    return np.random.default_rng(0)
