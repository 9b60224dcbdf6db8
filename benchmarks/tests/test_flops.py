"""``flops.py`` against the paper's table, and ``peaks.py`` on an unknown
device."""
import json
import math
import os

import pytest

from benchmarks.harness import flops, peaks
from benchmarks.windows import fit

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _count(num_layers=50, batch=2):
    """The shipped configuration's symbol, at ``num_layers`` deep."""
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "resnet50.json")) as f:
        cfg = json.load(f)
    cfg["symbol"]["kwargs"]["num_layers"] = num_layers
    sym = fit.build_symbol(cfg, rehearse=False)
    _, params, _, nodes, shapes = fit.shapes_of(sym, cfg, cfg["dtypes"],
                                                batch, False)
    n_params = sum(math.prod(s) for s, _ in params.values())
    return cfg, nodes, shapes, n_params


# He et al. 2015, Table 1: "FLOPs" there are multiply-adds. The table's
# model strides in the 3x3 convolution of a unit; MXNet's v1 symbol strides
# in the first 1x1, which makes the three down-sampling units cheaper, so
# the count stands a few percent under the table. The 152-layer net is the
# same symbol function and has no cell yet (PERF.md, Open questions).
@pytest.mark.parametrize("num_layers,table_macs,table_params",
                         [(50, 3.8e9, 25557032), (152, 11.3e9, 60192808)])
def test_forward_macs_against_the_table(num_layers, table_macs,
                                        table_params):
    cfg, nodes, shapes, n_params = _count(num_layers, batch=2)
    macs = flops.forward_macs(nodes, shapes) / 2
    assert 0.90 * table_macs <= macs <= 1.08 * table_macs, macs
    assert n_params == table_params
    if num_layers == cfg["num_layers"]:
        assert n_params == cfg["parameters"]
    assert flops.train_step_flops(nodes, shapes) == 6 * 2 * macs


def test_least_seconds_is_the_larger_bound_per_pass():
    _, nodes, shapes, _ = _count(batch=4)
    total, by_flops, by_bytes = flops.conv_least_seconds(
        nodes, shapes, 197e12, 819e9)
    assert total >= max(by_flops, by_bytes)
    assert total <= by_flops + by_bytes
    # a chip with endless bandwidth is bound by arithmetic alone
    only_flops, f2, _ = flops.conv_least_seconds(nodes, shapes, 197e12, 1e30)
    assert only_flops == pytest.approx(f2)


def test_unknown_device_kind_raises():
    assert peaks.peaks("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError):
        peaks.peaks("cpu")
