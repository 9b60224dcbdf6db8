"""``metrics/attn.fwd_ms.py``: the device time of the attention forward
kernel alone, the count that says whether a layer's checkpoint segment
kept the kernel's output (one run a layer) or made it again (two); no
reading without a trace or where the cell is no language model's."""
import types

import pytest

from benchmarks.harness import lm_trace
from benchmarks.harness.files import load_file, read_json

NAME = "attn.fwd_ms"


def _read(run, rows=None):
    trace = None
    if rows is not None:
        times = lm_trace.NodeTimes.__new__(lm_trace.NodeTimes)
        times.rows = rows
        trace = types.SimpleNamespace(node_times=times)
    reader = load_file("benchmarks/metrics/%s.py" % NAME, "reader")
    return reader.read({"run": run, "trace": trace, "peaks": None})


def _layer(node, recomputed):
    """A layer's attention ops as the chip's trace names them: the scope
    path ends at ``pallas_call`` and the kernel's name is the
    instruction's; the backward kernels and a transpose beside it."""
    fwd = "jit(train_step)/forward/checkpoint/%s/jvp(flash_attention_fwd)/" \
          "pallas_call" % node
    again = "jit(train_step)/backward/checkpoint/rematted_computation/%s/" \
            "jvp(flash_attention_fwd)/pallas_call" % node
    bwd = "jit(train_step)/backward/transpose(jvp(%s))/pallas_call" % node
    rows = [(node, fwd, "%jvp_flash_attention_fwd_.1", 4e-3),
            (node, bwd, "%flash_attention_dq.1", 3e-3),
            (node, bwd, "%flash_attention_dkv.1", 5e-3),
            (node, "jit(train_step)/forward/checkpoint/%s/transpose" % node,
             "%copy.7", 1e-3)]
    if recomputed:
        rows.append((node, again, "%jvp_flash_attention_fwd_.2", 4e-3))
    return rows


@pytest.mark.parametrize("run, rows", [
    ({"trace": None, "chips": 1}, _layer("l0_attn_core", True)),
    ({"lm": {}}, None),
], ids=["another_cell", "no_trace"])
def test_nothing_to_read_gives_none(run, rows):
    assert _read(run, rows) is None


@pytest.mark.parametrize("recomputed, want", [(True, 16.0), (False, 8.0)],
                         ids=["made_again", "kept"])
def test_counts_the_forward_kernel_under_the_attention_nodes(recomputed,
                                                             want):
    rows = _layer("l0_attn_core", recomputed) \
        + _layer("l1_attn_core", recomputed) + [
        # a kernel of that name under another node is not attention's
        ("l1_moe", "forward/l1_moe/flash_attention_fwd", "%fusion.3", 9e-3),
        ("", "", "%flash_attention_fwd.9", 9e-3)]
    assert abs(_read({"lm": {}}, rows) - want) < 1e-9
    # without the kernel (a program that takes it off the path): nothing
    rest = [r for r in rows if "flash_attention_fwd" not in r[1] + r[2]]
    assert _read({"lm": {}}, rest) is None


def test_entry_lists_the_cell():
    by_name = {m["name"]: m for m in read_json("BENCHMARK.json")["per_layer"]}
    assert by_name[NAME] == {
        "name": NAME, "unit": "ms", "better": "lower",
        "source": "device_trace", "layer": "kernels",
        "moves": "train_img_s", "workloads": ["trinity_mini.fit"]}
