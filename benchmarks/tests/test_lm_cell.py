"""The language-model cell on the CPU: its rehearsal line carries every
metric that needs no device trace, and ``correct`` comes out false for the
control and for every planted fault (``reference/afmoe.py`` ``FAULTS``,
half of the tokens left out, a state or an auxiliary state returned
unchanged), here under the rehearsal's limits and, by
``test_faults.test_committed_limits_fail_what_the_chip_read``, under the
committed ones for what the chip read."""
import json
import os

import pytest

from benchmarks.harness.files import load_file, read_json

CELL = "trinity_mini.fit"


def test_rehearsal_line_carries_every_metric_without_a_device_trace(capsys):
    from benchmarks import run
    run.main(["--workload", CELL, "--seed", str(2 ** 31 + 5), "--seconds",
              "1", "--trace", "1", "--rehearse"])
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] is True and line["rehearsal"] is True
    bench = read_json("BENCHMARK.json")
    mine = [m for m in bench["per_layer"]
            if "workloads" not in m or CELL in m["workloads"]]
    # shares of the chip's peak need the chip's peak
    want = {m["name"] for m in mine if m["source"] != "device_trace"
            and not m["name"].endswith(".mfu")}
    assert "moe.load_max_over_mean" in want and "fit.feed_ms" in want
    assert set(line["metrics"]) == want
    assert line["metrics"]["fit.dispatches_per_batch"]["value"] == 1.0
    assert line["metrics"]["compile.in_window"]["value"] == 0.0
    # top-2 of 8 experts, 4 held: the fullest held expert over the mean
    assert 1.0 <= line["metrics"]["moe.load_max_over_mean"]["value"] <= 4.0
    # the metrics the accepted cell alone reports stay off this line
    assert "train_step.mfu" not in line["metrics"]


def test_new_metrics_list_the_cell_and_nothing_else_changed():
    bench = read_json("BENCHMARK.json")
    new = {"lm_step.mfu", "attn.ms", "attn_roofline", "moe.ms",
           "moe_gmm_roofline", "moe.dispatch_ms", "moe.load_max_over_mean"}
    by_name = {m["name"]: m for m in bench["per_layer"]}
    assert new <= set(by_name)
    assert [m["name"] for m in bench["per_layer"]][-len(new):] == [
        "lm_step.mfu", "attn.ms", "attn_roofline", "moe.ms",
        "moe_gmm_roofline", "moe.dispatch_ms", "moe.load_max_over_mean"]
    for name in new:
        assert by_name[name]["workloads"] == [CELL]
        assert os.path.exists(os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "metrics", name + ".py"))
    for name in ("train_step.mfu", "conv_roofline"):
        assert by_name[name]["workloads"] == ["resnet50.fit"]
    assert bench["workloads"][-1]["name"] == CELL
    assert bench["workloads"][-1]["traffic"] == "fit_lm.s8k"
    traffic = read_json("benchmarks", "traffic", "fit_lm.s8k.json")
    assert (traffic["seq_len"], traffic["sequences_per_step"],
            traffic["pool_batches"], traffic["warmup_steps"],
            traffic["compared_steps"], traffic["trace_steps"],
            traffic["kvstore"], traffic["eval_metric"]) == (
        8192, 1, 8, 3, 3, 12, "local", "loss")


def test_readers_read_nothing_without_a_trace_or_on_another_cell():
    """A run of the accepted cell, or of a program without the scopes,
    gives every new reader ``None``, never an error."""
    for name in ("lm_step.mfu", "attn.ms", "attn_roofline", "moe.ms",
                 "moe_gmm_roofline", "moe.dispatch_ms",
                 "moe.load_max_over_mean"):
        reader = load_file("benchmarks/metrics/%s.py" % name, "reader")
        ctx = {"run": {"trace": None, "chips": 1}, "trace": None,
               "peaks": None}
        assert reader.read(ctx) is None, name


def test_control_and_planted_faults_are_not_correct(capsys):
    tool = load_file("benchmarks/tools/limits_faults.py", "limits_faults")
    tool.main(["--workload", CELL, "--seeds", "7", "--control-seeds", "7",
               "--rehearse"])
    row = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    sides = {p["side"]: p for p in row["proved"]}
    assert set(sides) == {"program", "control", "half_batch",
                          "no_routed_experts", "no_window", "rope_on_full",
                          "state_unchanged", "bn_stats_unchanged"}
    for side, p in sides.items():
        assert p["correct"] is (side == "program"), (side, p["numbers"])
        assert bool(p["over"]) is (side != "program")
    # a selection bias never written back reads 1, by the measure itself
    assert sides["bn_stats_unchanged"]["over"] == ["bn_stats"]
    assert sides["bn_stats_unchanged"]["numbers"]["bn_stats.total"] == 1.0
