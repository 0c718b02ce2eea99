"""Inner test suite run in a subprocess with 8 fake CPU devices.

Never collected by the outer run (see tests/test_multidevice.py and
pyproject's norecursedirs) so the main suite keeps 1 device.
"""
import os
import sys

# must run before jax initializes — this conftest is imported first in the
# subprocess pytest invocation
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "..", "src"))

import jax
import pytest

from repro.compat import make_mesh


@pytest.fixture(scope="session")
def mesh8():
    """(pod=2, data=2, model=2) production-mesh miniature."""
    assert len(jax.devices()) == 8, "inner suite needs 8 fake devices"
    return make_mesh((2, 2, 2), ("pod", "data", "model"))


@pytest.fixture(scope="session")
def rng():
    return jax.random.PRNGKey(0)
