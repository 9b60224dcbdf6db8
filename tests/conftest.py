"""Test configuration: run on a virtual 8-device CPU mesh.

Mirrors the reference's strategy of exercising distributed code without a
cluster (SURVEY.md §4: tools/launch.py --launcher local). Here the
XLA host-platform device-count flag gives 8 virtual devices so sharding/
collective tests run anywhere; the driver separately dry-runs the
multi-chip path via __graft_entry__.dryrun_multichip.
"""
import os

_TPU_LANE = os.environ.get("MXTPU_TEST_PLATFORM") == "tpu"

if not _TPU_LANE:
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    flags = os.environ.get("XLA_FLAGS", "")
    if "--xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

if not _TPU_LANE:
    # the suite runs on the virtual CPU mesh even where the caller's
    # environment names another platform (the tests/tpu lane lifts this)
    jax.config.update("jax_platforms", "cpu")
# numeric parity tests compare against numpy float32; disable bf16 matmul
jax.config.update("jax_default_matmul_precision", "highest")


import numpy as _np
import pytest as _pytest


@_pytest.fixture(autouse=True)
def _deterministic_seed():
    """Seed all RNG per test: initializer draws use np.random and eager
    random ops use the mx global key — cross-test order must not matter."""
    _np.random.seed(0)
    import mxnet_tpu as _mx
    _mx.random.seed(0)
    yield


def pytest_configure(config):
    config.addinivalue_line("markers", "slow: full example sweeps (nightly)")


def pytest_collection_modifyitems(config, items):
    if config.getoption("-m"):
        return
    skip_slow = _pytest.mark.skip(reason="slow: run with -m slow")
    for item in items:
        if "slow" in item.keywords:
            item.add_marker(skip_slow)


_TPU_LANE_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)), "tpu")


def pytest_collection_finish(session):
    session.config._mxtpu_nonlane_collected = sum(
        1 for item in session.items
        if not str(item.fspath).startswith(_TPU_LANE_DIR + os.sep))


def pytest_sessionfinish(session, exitstatus):
    # Tripwire: a run where main-suite tests were collected but ZERO
    # executed is a broken gate, not a green suite (the round-2 tests/tpu
    # conftest bug silently skipped all 301 tests). An all-skip run of the
    # TPU lane alone on a CPU-only host is legitimate, so only tests
    # outside tests/tpu count; --collect-only legitimately runs nothing.
    reporter = session.config.pluginmanager.get_plugin("terminalreporter")
    if reporter is None or exitstatus != 0 or session.config.option.collectonly:
        return
    ran = sum(len(reporter.stats.get(k, ())) for k in ("passed", "failed", "error"))
    nonlane = getattr(session.config, "_mxtpu_nonlane_collected", 0)
    if nonlane > 0 and ran == 0:
        reporter.write_line(
            "TRIPWIRE: %d non-TPU-lane tests collected but none executed — "
            "test gate is broken" % nonlane, red=True)
        session.exitstatus = 1
