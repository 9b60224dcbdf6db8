"""``benchmarks/run.py`` end to end on the CPU at a rehearsal size, for
each cell; and its refusal to measure without a chip."""
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
RUN = [sys.executable, os.path.join(ROOT, "benchmarks", "run.py")]


def _env(devices=1):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=%d" % devices
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    return env


def _cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return [(c["name"], c["chips"]) for c in json.load(f)["workloads"]]


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("name,chips", _cells())
def test_rehearsal_line(name, chips, trace):
    out = subprocess.run(
        RUN + ["--workload", name, "--seed", str(2 ** 31 + 17),
               "--seconds", "1", "--trace", str(trace), "--rehearse"],
        env=_env(chips), cwd=ROOT, capture_output=True, text=True,
        timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert set(line) >= {"correct", "attempted", "failed", "metrics",
                         "device"}
    assert list(line)[-1] == "compared"
    assert line["rehearsal"] is True
    assert line["device"]["platform"] == "cpu"
    assert line["device"]["count"] == chips
    assert line["correct"] is True, out.stderr[-3000:]
    assert line["attempted"] >= 2 and line["failed"] == 0
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    by_name = {m["name"]: m for m in bench["end_to_end"] + bench["per_layer"]}
    group = {m["name"] for m in
             (bench["per_layer"] if trace else bench["end_to_end"])}
    assert line["metrics"] and set(line["metrics"]) <= group
    for m, v in line["metrics"].items():
        # a rehearsal writes no device metric
        assert by_name[m]["source"] != "device_trace"
        assert v["unit"] == by_name[m]["unit"]
        assert isinstance(v["value"], float)
    assert "memory_peak_bytes" not in line["device"]
    assert "busy_s" not in line["device"] and "breakdown" not in line
    # each number compared stands beside its limit, on stderr too
    for k, row in line["compared"].items():
        # the worst leaf is printed beside the median leaf, and not judged
        assert row["limit"] is not None or k.endswith((".worst", ".total"))
        assert row["limit"] is None or not k.endswith(".worst")
        assert ("compared %s" % k) in out.stderr


def test_no_chip_no_result():
    name = _cells()[0][0]
    out = subprocess.run(RUN + ["--workload", name, "--seed", "1",
                                "--seconds", "1", "--trace", "0"],
                         env=_env(), cwd=ROOT, capture_output=True,
                         text=True, timeout=300)
    assert out.returncode != 0
    assert out.stdout.strip() == ""


def test_nothing_but_the_benchmark_is_not_enough(tmp_path):
    """In a directory that holds only ``BENCHMARK.json`` and ``paths`` the
    command fails and prints no result."""
    import shutil
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmarks"),
                    tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns(".out", "__pycache__"))
    out = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", _cells()[0][0],
         "--seed", "1", "--seconds", "1", "--trace", "0", "--rehearse"],
        env=_env(), cwd=tmp_path, capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
