"""What test modules share. For the tier-1 lanes: one run of a probe's
lane into a directory of the test session, and the rule that a lane on
XLA's CPU backend reports counts, bytes and equality, never a rate. For
equality across shapes: the one tolerance, with its reason."""
import json
import os
import re
import subprocess
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# ROADMAP.md's first aim: a rate, a ratio of rates or a utilisation is
# read on the chip by benchmarks/run.py and nowhere else
RATE_KEY = re.compile(r"(_img_s|_tok_s|_req_s|speedup|_vs_cold)$")


def run_lane(tool, flag, out_dir, mesh_devices=None, timeout=900):
    """Run ``tools/<tool> <flag>`` once on the CPU and return its JSON.
    The probe exits 0 whenever it ran to the end; what it found is for
    the lane's tests to judge. ``mesh_devices`` forces that many
    virtual CPU devices; without it the lane sees one."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    env.pop("MXNET_FAULTS", None)
    if mesh_devices:
        env["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=%d"
                            % mesh_devices)
    art = os.path.join(str(out_dir), flag.strip("-") + ".json")
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", tool), flag,
         "--json-out", art],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        timeout=timeout, env=env, cwd=ROOT)
    assert proc.returncode == 0, proc.stdout[-3000:]
    with open(art) as f:
        return json.loads(f.read())


def rate_keys(obj, path=""):
    """Every key of a lane's JSON, at any depth, that names a rate."""
    found = []
    if isinstance(obj, dict):
        for key, value in obj.items():
            here = "%s.%s" % (path, key) if path else str(key)
            if RATE_KEY.search(str(key)):
                found.append(here)
            found.extend(rate_keys(value, here))
    elif isinstance(obj, list):
        for i, value in enumerate(obj):
            found.extend(rate_keys(value, "%s[%d]" % (path, i)))
    return found


# One function computed at two batch shapes, or under two shardings, is
# two XLA programs: since jax 0.9.0 XLA:CPU picks a dot tiling per
# shape, so the float32 accumulation order differs and the results
# differ in the last places OF THE LARGEST TERMS (a logit near nought is
# a cancelling sum, so its own ULP says nothing: 6.25563e-05 against
# 6.25544e-05 is 250 of its ULPs and 2 of the row's). The gap is
# therefore counted in float32 spacings of the compared arrays' largest
# magnitude. Measured over 20 seeds each (PR 31): 1.5 for the decode
# cell of test_decode.py, slot bucket 1 against 4; 3.0 for the decode
# lane's wider cell; 2.0 for test_partition.py's serving layer,
# replicated against mp=8. The limit is 4 x the largest of them, and it
# is for float32 alone: every other dtype read a gap of 0 on all 20
# seeds (bfloat16 rounds the difference away) and stays array_equal, as
# do programs at the SAME shape and sharding. Tokens and arg-max are
# compared exactly everywhere.
CROSS_SHAPE_ULPS = 12.0


def float32_ulps_at_scale(max_abs_diff, max_abs):
    """``max_abs_diff`` in float32 spacings at magnitude ``max_abs``."""
    if max_abs == 0:
        return 0.0 if max_abs_diff == 0 else float("inf")
    nmant = np.finfo(np.float32).nmant
    return float(max_abs_diff) / 2.0 ** (np.floor(np.log2(max_abs)) - nmant)


def assert_equal_across_shapes(a, b, what=""):
    """Results of one function from programs of different batch shape
    or sharding: same dtype and shape; float32 values within
    ``CROSS_SHAPE_ULPS`` with the same arg-max (see above), any other
    dtype bit for bit."""
    assert a.dtype == b.dtype, (what, a.dtype, b.dtype)
    a, b = np.asarray(a), np.asarray(b)
    assert a.shape == b.shape, (what, a.shape, b.shape)
    if a.dtype != np.float32:
        assert np.array_equal(a, b), what
        return
    assert np.array_equal(a.argmax(-1), b.argmax(-1)), what
    a64, b64 = a.astype(np.float64), b.astype(np.float64)
    gap = float32_ulps_at_scale(np.abs(a64 - b64).max(),
                                max(np.abs(a64).max(), np.abs(b64).max()))
    assert gap <= CROSS_SHAPE_ULPS, (what, gap)
