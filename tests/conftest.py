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
_LOCKCHECK, _TRACECHECK = (
    os.environ.get(name, "") not in ("", "0")
    for name in ("IOTML_LOCKCHECK", "IOTML_TRACECHECK"))
if _LOCKCHECK:
    from iotml.analysis import lockcheck as _lockcheck

    _lockcheck.install()


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: left out of tier-1 (`-m 'not slow'`)")
    if (_LOCKCHECK or _TRACECHECK) \
            and not config.pluginmanager.has_plugin("iotml-lockcheck"):
        from iotml.analysis import pytest_plugin

        config.pluginmanager.register(pytest_plugin, "iotml-lockcheck")


@pytest.fixture
def rng():
    return np.random.default_rng(42)


# --------------------------------------- the registry a worker holds
# `obs.metrics.MAX_LABEL_SERIES` bounds what ONE deployment's process
# holds of a metric; a worker's registry holds what every deployment its
# tests built has left (PR 45: 530 `iotml_consumer_lag_records{group,
# topic,partition}` over six workers, 272 in one on the parent's run, at
# most 30 any one test's).  So every test is held to the bound on what
# IT adds, and the tests that read the whole registry against it take
# `one_deployments_registry` first.
def _series() -> dict:
    """family → its label sets in the default registry."""
    from iotml.obs import metrics

    return {name: m._series if isinstance(m, metrics.Histogram) else m._vals
            for name, m in list(metrics.default_registry._metrics.items())}


@pytest.fixture(autouse=True)
def _a_test_adds_a_bounded_number_of_series():
    from iotml.obs.metrics import MAX_LABEL_SERIES

    before = {name: len(sets) for name, sets in _series().items()}
    yield
    assert not {name: len(sets) - before.get(name, 0)
                for name, sets in _series().items()
                if len(sets) - before.get(name, 0) > MAX_LABEL_SERIES}


@pytest.fixture
def one_deployments_registry():
    """The default registry without the counters' and gauges' series that
    name a topic or a group: deployments earlier tests built and closed."""
    from iotml.obs.metrics import default_registry

    for metric in list(default_registry._metrics.values()):
        with metric._lock:
            sets = getattr(metric, "_vals", {})   # no histogram has one
            for key in [key for key in sets
                        if {"topic", "group"} & {label for label, _ in key}]:
                del sets[key]
