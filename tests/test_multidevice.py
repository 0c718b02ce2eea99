"""Runs the multi-device SP suite in ONE subprocess with 8 fake devices.

The outer pytest run keeps 1 device (assignment requirement); the inner
run sets XLA_FLAGS before jax initializes.  pyproject excludes
tests/multidevice from outer collection.

The inner suite is split by the ``slow`` marker: the default run skips
the heaviest e2e tests (they have a dedicated CI job — see the ``slow``
job in .github/workflows/ci.yml) so the tier-1 ``python -m pytest -x -q``
stays inside its time budget.  Set ``RUN_SLOW_TESTS=1`` to run the slow
set (``test_multidevice_slow_suite``) locally.
"""
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(__file__)


def _run_inner(marker_expr: str) -> None:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    # this process has already loaded JAX: on a TPU host it holds the chip,
    # so the child must stay on the CPU
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.path.join(HERE, "..", "src")
    proc = subprocess.run(
        [sys.executable, "-m", "pytest",
         os.path.join(HERE, "multidevice"), "-q", "-p", "no:cacheprovider",
         "-m", marker_expr],
        env=env, capture_output=True, text=True, timeout=1800,
    )
    if proc.returncode != 0:
        tail = "\n".join((proc.stdout + proc.stderr).splitlines()[-60:])
        pytest.fail(f"inner multidevice suite failed:\n{tail}")


@pytest.mark.timeout(1800)
def test_multidevice_suite():
    _run_inner("not slow")


@pytest.mark.timeout(1800)
@pytest.mark.skipif(os.environ.get("RUN_SLOW_TESTS") != "1",
                    reason="slow e2e set runs in the dedicated CI job "
                           "(RUN_SLOW_TESTS=1 to run locally)")
def test_multidevice_slow_suite():
    _run_inner("slow")
