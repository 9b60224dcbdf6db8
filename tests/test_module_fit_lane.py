"""Tier-1 smoke lanes for the user-facing Module.fit path.

Runs ``tools/module_fit_probe.py --fit-smoke`` (CPU backend, tiny MLP,
20 batches) as a subprocess and pins the two acceptance numbers:

- the fused whole-step program issues <= 2 jitted-program dispatches per
  batch (it is 1 today), the phase-split oracle exactly 3;
- fused Module.fit throughput >= the IN-RUN RECALIBRATED gate: the
  probe predicts the achievable speedup from the split leg's own phase
  spans (fused removes the dispatch chain, everything else stays) and
  gates at 70% of that, clamped to [1.2, 3.0] — the absolute >=3x gate
  false-failed on share-throttled boxes (2.4x at seed there) where
  inflated non-dispatch overhead shrinks the achievable ratio.

And ``--dp-smoke`` (the 8-device virtual CPU mesh): the fused SPMD
data-parallel step must issue EXACTLY 1 dispatch per batch and be at
least as fast as the kvstore phase-split path.

And ``--mp-smoke`` (the same mesh laid out 2x4 dp x mp with every
parameter rule-sharded over mp): 1 fused dispatch per batch, zero
fused fallbacks, per-device committed param bytes ~ 1/mp of the
replicated layout per the buffer ledger, fused >= phase-split.

The probes' JSON lands as artifacts (``$MXTPU_ARTIFACT_DIR/
module_fit_smoke.json`` / ``module_fit_dp_smoke.json``, default
/tmp/mxtpu_artifacts) so the img/s trajectory of the CPU lane is
captured every round, chip or no chip.
"""
import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_probe(art, lane_flag="--fit-smoke"):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    # the fit lane measures single-program dispatch (the probe sets its
    # own virtual-mesh flag for --dp-smoke)
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "module_fit_probe.py"),
         lane_flag, "--json-out", art],
        stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
        text=True, timeout=420, env=env, cwd=ROOT)
    assert proc.returncode == 0, proc.stdout[-2000:]
    with open(art) as f:
        return json.loads(f.read())


def test_module_fit_smoke_lane():
    art_dir = os.environ.get("MXTPU_ARTIFACT_DIR", "/tmp/mxtpu_artifacts")
    os.makedirs(art_dir, exist_ok=True)
    art = os.path.join(art_dir, "module_fit_smoke.json")
    try:
        out = _run_probe(art)
    except AssertionError:
        # epochs are ~10ms windows on share-throttled CI boxes — one
        # re-measure before declaring a throughput regression
        out = _run_probe(art)
    assert out["lane"] == "module_fit_smoke"
    fused, split = out["fused"], out["phase_split"]
    # the dispatch counts are the deterministic regression guard — any
    # extra program sneaking into either inner loop fails regardless of
    # timing noise
    assert fused["dispatches_per_batch"] <= 2.0, out
    assert split["dispatches_per_batch"] == 3.0, out
    assert fused["img_s"] > 0 and split["img_s"] > 0
    # the probe gates the throughput ratio against its in-run
    # recalibrated expectation and stamps the artifact; the gate value
    # itself must be sane (never laxer than 1.2x, never stricter than
    # the old absolute 3x)
    assert out["gates_passed"] is True, out
    assert 1.2 <= out["fit_gate"] <= 3.0, out
    assert out["fit_speedup"] >= out["fit_gate"], out
    assert out["fit_speedup_expected"] >= 1.0, out


def test_module_fit_mp_smoke_lane():
    """The dp x mp partition-rule lane (ISSUE 15 acceptance): tiny MLP
    on the 8-device CPU mesh as a 2x4 dp x mp layout, every parameter
    rule-sharded over mp. The probe gates 1 fused dispatch/batch, zero
    fused fallbacks, ledger param bytes per device ~ 1/mp of
    replicated, and fused >= phase-split; one re-measure under CI
    noise like the other lanes."""
    art_dir = os.environ.get("MXTPU_ARTIFACT_DIR", "/tmp/mxtpu_artifacts")
    os.makedirs(art_dir, exist_ok=True)
    art = os.path.join(art_dir, "module_fit_mp_smoke.json")
    try:
        out = _run_probe(art, "--mp-smoke")
    except AssertionError:
        out = _run_probe(art, "--mp-smoke")  # one retry under CI noise
    assert out["lane"] == "module_fit_mp_smoke"
    assert out["mesh_axes"] == {"dp": 2, "mp": 4}
    assert out["gates_passed"] is True, out
    assert out["fused"]["dispatches_per_batch"] == 1.0, out
    assert out["fused"]["dispatch_counts"] == {
        "train_step": out["nbatch"]}, out
    assert out["phase_split"]["dispatches_per_batch"] == 3.0, out
    assert out["mp_speedup"] >= 1.0, out
    led = out["ledger"]
    assert led["ratio"] <= 1.5 / led["mp"], led


def test_module_fit_dp_smoke_lane():
    """The data-parallel lane (ISSUE 2 acceptance): tiny MLP on the
    8-device virtual CPU mesh, fused-SPMD vs kvstore phase-split. The
    probe itself asserts the two gates — exactly 1 dispatch/batch on
    the fused path and dp-fused >= phase-split img/s — and banks the
    JSON artifact; timing noise gets one re-measure like the fit lane."""
    art_dir = os.environ.get("MXTPU_ARTIFACT_DIR", "/tmp/mxtpu_artifacts")
    os.makedirs(art_dir, exist_ok=True)
    art = os.path.join(art_dir, "module_fit_dp_smoke.json")
    try:
        out = _run_probe(art, "--dp-smoke")
    except AssertionError:
        out = _run_probe(art, "--dp-smoke")  # one retry under CI noise
    assert out["lane"] == "module_fit_dp_smoke"
    assert out["n_devices"] >= 2
    assert out["gates_passed"] is True, out
    assert out["fused"]["dispatches_per_batch"] == 1.0, out
    assert out["phase_split"]["dispatches_per_batch"] == 3.0, out
    assert out["dp_speedup"] >= 1.0, out
