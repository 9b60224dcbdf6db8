"""``metrics/moe.combine_ms.py``: the device time of the routed layer's
combine kernel, by the kernel's name wherever the trace files it; no
reading without a trace, on a cell that is no language model's, or where
no such kernel ran (a program without it)."""
import types

import pytest

from benchmarks.harness import lm_trace
from benchmarks.harness.files import load_file, read_json

NAME = "moe.combine_ms"


def _read(run, rows=None):
    trace = None
    if rows is not None:
        times = lm_trace.NodeTimes.__new__(lm_trace.NodeTimes)
        times.rows = rows
        trace = types.SimpleNamespace(node_times=times)
    reader = load_file("benchmarks/metrics/%s.py" % NAME, "reader")
    return reader.read({"run": run, "trace": trace, "peaks": None})


def _layer(node):
    """A routed layer's ops as the chip's trace names them: the combine
    forward and made again under the layer's node, the dispatch's
    transpose with no scope path, and the layer's other ops."""
    fwd = "jit(train_step)/forward/checkpoint/%s/jvp(jit(held_experts_ffn))" \
          "/combine/moe_combine/pallas_call" % node
    again = "jit(train_step)/backward/checkpoint/rematted_computation/%s/" \
            "jvp(jit(held_experts_ffn))/combine/moe_combine/pallas_call" % node
    return [(node, fwd, "%moe_combine.1", 1e-3),
            (node, again, "%moe_combine.2", 1e-3),
            ("", "", "%moe_combine.3", 2e-3),
            (node, "jit(train_step)/forward/checkpoint/%s/jvp(jit("
             "held_experts_ffn))/dispatch/while" % node, "%while.4", 5e-3),
            ("", "", "%ragged-dot.5", 9e-3)]


@pytest.mark.parametrize("run, rows", [
    ({"trace": None, "chips": 1}, _layer("l1_moe")),
    ({"lm": {}}, None),
], ids=["another_cell", "no_trace"])
def test_nothing_to_read_gives_none(run, rows):
    assert _read(run, rows) is None


def test_counts_the_kernel_wherever_the_trace_files_it():
    rows = _layer("l1_moe") + _layer("l2_moe")
    assert abs(_read({"lm": {}}, rows) - 8.0) < 1e-9
    # the parent's program: the combine is a gather and a sum, no kernel
    rest = [r for r in rows if "moe_combine" not in r[1] + r[2]]
    assert _read({"lm": {}}, rest) is None


def test_entry_lists_the_routed_cells():
    by_name = {m["name"]: m for m in read_json("BENCHMARK.json")["per_layer"]}
    assert by_name[NAME] == {
        "name": NAME, "unit": "ms", "better": "lower",
        "source": "device_trace", "layer": "kernels",
        "moves": "train_img_s", "workloads": [
            "trinity_mini.fit", "nemotron3_nano.fit", "kimi_linear.fit",
            "lfm2_moe.fit"]}
