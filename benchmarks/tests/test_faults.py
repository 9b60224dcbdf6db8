"""``correct`` has to come out false: for the control (the reference in
the program's place, operands rounded to 8-bit floats and the optimizer's
state held in bf16), and for a run whose timed
path is broken underneath: a step that returns its state unchanged, one
that never writes the batch-norm moving statistics back, and half of the
batch left out (the mean taken over the rest). And the limits that are
committed have to fail every control and fault reading that was taken on
the chip at the cell's own size (``limits/<cell>.json``, ``proved``).

The faults are planted in ``Module`` from here; the harness is driven
through ``run.main`` with ``--rehearse``, which skips only its look for a
chip."""
import json
import os

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _run(capsys, workload="resnet50.fit", seed=7):
    from benchmarks import run
    run.main(["--workload", workload, "--seed", str(seed), "--seconds", "0.5",
              "--trace", "0", "--rehearse"])
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def _share_of_batch(share):
    """Every step sees only the first ``share`` of its rows, repeated."""
    import mxnet_tpu as mx
    from mxnet_tpu.io import DataBatch
    orig = mx.mod.Module._run_fused_step

    def broken(self, plan, packed, data_batch, eval_metric):
        def cut(a):
            a = np.asarray(a)
            n = int(len(a) * share)
            return np.concatenate([a[:n]] * int(round(1 / share)))
        batch = DataBatch([cut(a) for a in data_batch.data],
                          [cut(a) for a in data_batch.label], pad=0)
        return orig(self, plan, packed, batch, eval_metric)
    return orig, broken


def _state_unchanged(only_statistics=False):
    """The step runs, and hands back the state it was given (or, of the
    state, only the moving statistics)."""
    import mxnet_tpu as mx
    orig = mx.mod.Module._run_fused_step

    def broken(self, plan, packed, data_batch, eval_metric):
        import jax.numpy as jnp
        ex = self._exec     # copies: the step donates what it is given
        params = {} if only_statistics else {
            n: jnp.copy(ex.arg_dict[n]._data) for n in self._param_names}
        states = [] if only_statistics else [
            tuple(jnp.copy(x._data) for x in tup) for tup in packed]
        aux = [jnp.copy(a._data) for a in ex.aux_arrays]
        ok = orig(self, plan, packed, data_batch, eval_metric)
        for n, v in params.items():
            ex.arg_dict[n]._set_data(v)
        for tup, old in zip(packed, states):
            for x, v in zip(tup, old):
                x._set_data(v)
        for a, v in zip(ex.aux_arrays, aux):
            a._set_data(v)
        return ok
    return orig, broken


FAULTS = {"state_unchanged": _state_unchanged,
          "bn_stats_unchanged": lambda: _state_unchanged(True),
          "half_batch": lambda: _share_of_batch(0.5)}


def test_sound_run_is_correct(capsys):
    line = _run(capsys)
    assert line["correct"] is True, line["compared"]


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_broken_step_is_not_correct(capsys, monkeypatch, fault):
    import mxnet_tpu as mx
    orig, broken = FAULTS[fault]()
    monkeypatch.setattr(mx.mod.Module, "_run_fused_step", broken)
    line = _run(capsys)
    assert line["correct"] is False, line["compared"]
    over = [k for k, r in line["compared"].items()
            if r["limit"] is not None and r["value"] > r["limit"]]
    assert over, line["compared"]


def test_the_control_is_not_correct():
    """The reference one step below the stated precisions, judged as the
    program would be."""
    import argparse
    import time
    from benchmarks.harness import correct
    from benchmarks.windows import fit

    def read(*parts):
        with open(os.path.join(ROOT, *parts)) as f:
            return json.load(f)

    cfg = read("benchmarks", "configs", "resnet50.json")
    traffic = read("benchmarks", "traffic", "fit.b256.json")
    limits = read("benchmarks", "limits", "resnet50.fit.json")["rehearse"]
    args = argparse.Namespace(seed=9, seconds=0.0, trace=0, rehearse=True)
    res = fit.run({"name": "resnet50.fit", "chips": 1}, cfg, traffic, args,
                  {"t_process": time.perf_counter(),
                   "readings_only": True})
    reference = fit.load_file(cfg["reference"]["file"], "bench_reference")
    kwargs, ri = cfg["reference"]["kwargs"], res["reference_inputs"]
    ref = correct.reference_readings(reference, kwargs, ri)
    ctl = correct.reference_readings(reference, kwargs, ri,
                                     **reference.CONTROL)
    ok, table = correct.judge(correct.compare(ctl, ref), limits)
    assert ok is False, table
    ok, table = correct.judge(correct.compare(res["program"], ref), limits)
    assert ok is True, table


def _cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return [w["name"] for w in json.load(f)["workloads"]]


@pytest.mark.parametrize("cell", _cells())
def test_committed_limits_fail_what_the_chip_read(cell):
    """``tools/limits.py`` judged the control and every fault on the chip
    at the cell's own size and its rows are kept; whoever changes a limit
    has to keep every one of them failed, and every sound run passed."""
    from benchmarks.harness import correct
    with open(os.path.join(ROOT, "benchmarks", "limits",
                           cell + ".json")) as f:
        kept = json.load(f)
    rows = kept["proved"]["runs"]
    sides = {r["side"] for r in rows}
    assert sides >= {"program", "control", "half_batch", "state_unchanged",
                     "bn_stats_unchanged"}
    assert len({r["seed"] for r in rows if r["side"] == "control"}) >= 3
    for r in rows:
        ok, table = correct.judge(
            {k: (v, "") for k, v in r["numbers"].items()}, kept["limits"])
        assert ok is (r["side"] == "program"), (r["seed"], r["side"], table)
        assert ok is r["correct"]
