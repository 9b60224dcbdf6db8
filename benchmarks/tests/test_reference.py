"""``reference/resnet.py`` against ``Module``: forward, loss, backward and
one update in float32 on the CPU, at the 50-layer and the 152-layer unit
lists (the second has no cell yet; it is the same symbol function). In
float32 the two agree to rounding, which ties the reference's equations
(biased batch variance, the v1 stride, the momentum update) to the
program's before the chip compares them in bf16."""
import argparse
import json
import os
import time

import pytest

from benchmarks.harness import correct
from benchmarks.windows import fit

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


@pytest.mark.parametrize("num_layers,units", [(50, [3, 4, 6, 3]),
                                              (152, [3, 8, 36, 3])])
def test_reference_against_module_float32(num_layers, units):
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "resnet50.json")) as f:
        cfg = json.load(f)
    cfg["rehearse"]["symbol_kwargs"]["num_layers"] = num_layers
    cfg["reference"]["kwargs"]["units"] = units
    cfg["rehearse"]["dtypes"] = {"data": "float32"}
    # a small step: at this size lr 0.05 leaves the linear regime at once,
    # and the test is of the equations, not of the conditioning
    cfg["rehearse"]["optimizer"] = {"learning_rate": 0.002}
    traffic = {"window": "fit", "batch_per_chip": 8, "chips": 1,
               "kvstore": "local", "pool_batches": 2, "warmup_steps": 2,
               "compared_steps": 2, "eval_metric": "acc", "trace_steps": 0,
               "rehearse": {"batch_per_chip": 8}}
    cell = {"name": "resnet%d.fit" % num_layers, "chips": 1}
    args = argparse.Namespace(seed=5, seconds=0.0, trace=0, rehearse=True)
    res = fit.run(cell, cfg, traffic, args,
                  {"t_process": time.perf_counter(),
                   "readings_only": True})
    reference = fit.load_file(cfg["reference"]["file"], "bench_reference")
    ref = correct.reference_readings(reference, cfg["reference"]["kwargs"],
                                     res["reference_inputs"])
    assert len(ref["grad_norms"]) == len(res["program"]["grad_norms"])
    numbers = {k: v[0] for k, v in
               correct.compare(res["program"], ref).items()}
    # step 1 is a forward and a backward pass of identical parameters
    assert numbers["loss.1"] < 1e-5
    assert numbers["grad_norm"] < 2e-3
    # step 2's loss has seen one update (lr, momentum, rescale_grad)
    assert numbers["loss.2"] < 2e-2
