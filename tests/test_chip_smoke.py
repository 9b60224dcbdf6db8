"""``chip_smoke.py`` rehearsed on the CPU, and the compile-cache helper.

The chip itself is not here, so what these tests hold the script to is
the no-fallback rule (on the CPU it fails and never prints the success
line), that every phase runs end to end at a tiny size behind
``--rehearse`` (which can never print the success line either), and that
the four-chip path really spreads the batch over four devices.
"""
import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(ROOT, "chip_smoke.py")


def _run(args, tmp_path, devices=1):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache"),
               XLA_FLAGS="--xla_force_host_platform_device_count=%d"
                         % devices)
    env.pop("MXNET_COMPILE_CACHE", None)
    # held to two cores: xdist runs the wall-clock-gated smoke lanes
    # beside this file, and a rehearsal compiles on every core it is given
    cores = ",".join(map(str, sorted(os.sched_getaffinity(0))[:2]))
    pin = ["taskset", "-c", cores] if shutil.which("taskset") else []
    proc = subprocess.run(pin + [sys.executable, SMOKE] + args,
                          cwd=str(tmp_path),
                          stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                          text=True, timeout=600, env=env)
    lines = [json.loads(l) for l in proc.stdout.splitlines()
             if l.startswith("{")]
    return proc, lines


def _succeeded(lines):
    return any(l.get("ok") is True for l in lines)


def test_fails_on_the_cpu_and_prints_no_result(tmp_path):
    """The no-fallback rule: with no accelerator the script exits
    non-zero before any phase and prints no result line at all."""
    proc, lines = _run([], tmp_path)
    assert proc.returncode != 0
    assert "no accelerator" in proc.stderr
    assert lines == []


def _default_cache_entries():
    default = os.path.join(ROOT, ".jax_cache")
    return sorted(os.listdir(default)) if os.path.isdir(default) else None


def test_rehearsal_runs_every_phase_but_never_succeeds(tmp_path):
    before = _default_cache_entries()
    proc, lines = _run(["--rehearse"], tmp_path)
    assert proc.returncode == 0, proc.stderr[-3000:]
    by_phase = {l["phase"]: l for l in lines if "phase" in l}
    assert set(by_phase) == {"start", "train", "serve", "decode", "kernels",
                             "total"}
    assert not any("failed" in l for l in lines)
    train = by_phase["train"]
    assert train["fused_fallback"] is None
    assert train["dispatches"] == {"dispatch.train_step": 3}
    assert train["compiles_after_warmup"] == 0
    assert train["losses"][-1] < train["losses"][0]
    assert by_phase["serve"]["compiles_after_warmup"] == 0
    assert by_phase["decode"]["size"] == "toy"
    assert by_phase["kernels"]["kernel_mode"] == "interpreted"
    # the switch can never lead to the success line
    assert not _succeeded(lines)
    assert lines[-1] == {"rehearsal": "passed",
                         "device": {"platform": "cpu", "kind": "cpu",
                                    "count": 1}}
    # the cache went where the environment said, and nowhere else
    assert by_phase["start"]["cache_dir"] == str(tmp_path / "jax_cache")
    assert os.listdir(tmp_path / "jax_cache")
    assert _default_cache_entries() == before


def test_four_chip_rehearsal_spreads_the_batch(tmp_path):
    proc, lines = _run(["--rehearse", "--chips", "4"], tmp_path, devices=4)
    assert proc.returncode == 0, proc.stderr[-3000:]
    by_phase = {l["phase"]: l for l in lines if "phase" in l}
    # that path and what it is compared with, and no other phase
    assert set(by_phase) == {"start", "one_chip_reference", "dp4",
                             "dp2_mp2", "total"}
    dp4 = by_phase["dp4"]
    assert len(set(dp4["devices"])) == 4
    assert len({dev for dev, _ in dp4["batch_shards"]}) == 4
    assert all(shape[0] == 4 for _, shape in dp4["batch_shards"])
    assert dp4["all_reduce"] is True and dp4["fused_fallback"] is None
    mp = by_phase["dp2_mp2"]
    assert 0.45 <= (mp["param_bytes_per_device"]
                    / mp["param_bytes_replicated"]) <= 0.6
    assert not _succeeded(lines)
    assert lines[-1]["device"]["count"] == 4


def test_four_chips_on_one_device_fails(tmp_path):
    proc, lines = _run(["--rehearse", "--chips", "4"], tmp_path)
    assert proc.returncode != 0 and not _succeeded(lines)


_PLACE = ("import mxnet_tpu.jax_cache as c, jax; d = c.place(); "
          "print(d); print(jax.config.jax_compilation_cache_dir)")


@pytest.mark.parametrize("env_dir", ["set", "unset"])
def test_cache_helper_places_one_directory(tmp_path, monkeypatch, env_dir):
    """Env set: that directory, and the helper updates no config (JAX read
    the variable itself). Env unset: the fixed path inside the checkout,
    the same in two processes — never a temporary name, a pid or a time."""
    from mxnet_tpu import jax_cache
    import jax
    if env_dir == "set":
        monkeypatch.setenv(jax_cache.ENV, str(tmp_path))
        updates = []
        monkeypatch.setattr(jax.config, "update",
                            lambda *a: updates.append(a))
        assert jax_cache.place() == str(tmp_path)
        assert updates == []
        return
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    env.pop(jax_cache.ENV, None)
    outs = [subprocess.run([sys.executable, "-c", _PLACE], env=env,
                           cwd=str(tmp_path), stdout=subprocess.PIPE,
                           text=True, timeout=120,
                           check=True).stdout.split()
            for _ in range(2)]
    want = os.path.join(ROOT, ".jax_cache")
    assert outs[0] == outs[1] == [want, want]
    assert jax_cache.DEFAULT_DIR == want
