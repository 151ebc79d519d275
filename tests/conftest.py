"""Test environment: force JAX onto CPU with 8 virtual devices.

Mirrors the reference's simulator-as-cluster trick (SURVEY §4.4): multi-chip
code paths are exercised on a virtual 8-device CPU mesh, no TPU required.
Must run before jax initializes, hence env vars at import time.
"""

import os
import sys

# Unit tests are hermetic and fast: CPU, set before jax is imported (the
# chip run is `python chip_smoke.py` through the chip tool).  Override
# with IOTML_TEST_PLATFORM=tpu to run a test file on the chip.
os.environ["JAX_PLATFORMS"] = os.environ.get("IOTML_TEST_PLATFORM", "cpu")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

# Repo root on sys.path so `import iotml` works without installation.
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402
import pytest  # noqa: E402

REFERENCE_ROOT = "/root/reference"


def reference_available() -> bool:
    return os.path.isdir(REFERENCE_ROOT)


requires_reference = pytest.mark.skipif(
    not reference_available(),
    reason="read-only reference checkout not mounted")

# IOTML_LOCKCHECK=1: run the whole suite under the runtime lock-order &
# race detector (iotml.analysis.lockcheck).  Installed at import time —
# before any test constructs a broker/server — so every lock the stream
# stack creates is instrumented; the registered plugin reports at session
# end and FAILS the run on lock-order cycles.  Equivalent to
# `pytest -p iotml.analysis.pytest_plugin`.
# IOTML_TRACECHECK=1: arm the JAX recompile guard over the known hot
# loops — a warmed loop that re-traces fails its test (same plugin,
# independently gated; see iotml.analysis.pytest_plugin).
if os.environ.get("IOTML_LOCKCHECK", "") not in ("", "0") \
        or os.environ.get("IOTML_TRACECHECK", "") not in ("", "0"):
    if os.environ.get("IOTML_LOCKCHECK", "") not in ("", "0"):
        from iotml.analysis import lockcheck as _lockcheck

        _lockcheck.install()

    def pytest_configure(config):
        if not config.pluginmanager.has_plugin("iotml-lockcheck"):
            from iotml.analysis import pytest_plugin

            config.pluginmanager.register(pytest_plugin, "iotml-lockcheck")


@pytest.fixture
def rng():
    return np.random.default_rng(42)
