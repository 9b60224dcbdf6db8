"""TPU test lane: runs on the REAL chip, skipped on CPU-only runs.

The reference validates its second backend by consistency against the
first (tests/python/gpu/test_operator_gpu.py + test_utils.check_consistency
at python/mxnet/test_utils.py:1267); this lane is the TPU analogue.

Run with (one process: a chip belongs to one process at a time):
    MXTPU_TEST_PLATFORM=tpu python -m pytest tests/tpu -q -p no:xdist

Under the default test run (`pytest tests/`) the root conftest pins the
cpu platform and everything here skips. Asked for by name on a machine
where JAX finds no accelerator, the lane is an error, not a skip: it
never falls back to the CPU.
"""
import os

import pytest


def _on_accelerator():
    import jax
    try:
        return any(d.platform != "cpu" for d in jax.devices())
    except RuntimeError:
        return False


_TPU_LANE_DIR = os.path.dirname(os.path.abspath(__file__))


def pytest_collection_modifyitems(config, items):
    # pytest hands EVERY conftest the whole session's item list — only mark
    # items that actually live under tests/tpu/, or `pytest tests/` would
    # skip the entire suite (round-2 regression).
    if os.environ.get("MXTPU_SWEEP_SELF") == "1":
        return  # cpu-vs-cpu case-spec debugging (test_op_sweep.SELF_MODE)
    lane = [item for item in items
            if str(item.fspath).startswith(_TPU_LANE_DIR + os.sep)]
    if os.environ.get("MXTPU_TEST_PLATFORM") == "tpu":
        if lane and not _on_accelerator():
            raise pytest.UsageError(
                "MXTPU_TEST_PLATFORM=tpu, but JAX finds no accelerator: "
                "the on-chip lane does not fall back to the CPU")
        return
    skip = pytest.mark.skip(
        reason="TPU lane: set MXTPU_TEST_PLATFORM=tpu with a chip attached")
    for item in lane:
        item.add_marker(skip)
