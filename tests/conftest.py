"""Shared fixtures.  NOTE: no XLA_FLAGS here — the main test run sees ONE
device (the assignment requires it); multi-device SP tests run in a
subprocess (tests/test_multidevice.py) with their own flags."""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import pytest

from repro.compat import make_mesh


@pytest.fixture(scope="session")
def mesh1():
    """1-device (data=1, model=1) mesh for smoke tests."""
    return make_mesh((1, 1), ("data", "model"))


@pytest.fixture(scope="session")
def rng():
    return jax.random.PRNGKey(0)
