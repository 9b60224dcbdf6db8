"""The Kimi Linear cell on the CPU: its rehearsal line carries every metric
that needs no device trace, its entries are appended and list the cell, and
``correct`` comes out false for the control and for every planted
fault."""
import json

from benchmarks.harness.files import load_file, read_json

CELL = "kimi_linear.fit"
NEW = ["kimi_step.mfu", "kda.ms", "kda.scan_ms", "kda_roofline",
       "mla_attn_roofline", "kda.chunks_per_step"]
#: the accepted metrics of the layers the cell shares with the two language
#: cells that were there: the cell is appended to their lists
SHARED = ["attn.ms", "attn.fwd_ms", "moe.ms", "moe.dispatch_ms",
          "moe.load_max_over_mean", "moe.chunks_per_pass",
          "moe.overflow_share", "moe_gmm_roofline"]


def test_rehearsal_line_carries_every_metric_without_a_device_trace(capsys):
    from benchmarks import run
    run.main(["--workload", CELL, "--seed", str(2 ** 31 + 36), "--seconds",
              "1", "--trace", "1", "--rehearse"])
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] is True and line["rehearsal"] is True
    bench = read_json("BENCHMARK.json")
    mine = [m for m in bench["per_layer"]
            if "workloads" not in m or CELL in m["workloads"]]
    want = {m["name"] for m in mine if m["source"] != "device_trace"
            and not m["name"].endswith(".mfu")}
    assert set(line["metrics"]) == want
    assert {"kda.chunks_per_step", "moe.chunks_per_pass",
            "moe.load_max_over_mean", "moe.overflow_share",
            "fit.feed_ms"} <= want
    assert line["metrics"]["fit.dispatches_per_batch"]["value"] == 1.0
    assert line["metrics"]["compile.in_window"]["value"] == 0.0
    # 2 sequences of 32 tokens in chunks of 16, four KDA layers
    assert line["metrics"]["kda.chunks_per_step"]["value"] == 2 * 2 * 4
    assert 1.0 <= line["metrics"]["moe.load_max_over_mean"]["value"] <= 4.0
    assert line["metrics"]["moe.chunks_per_pass"]["value"] == 1.0
    assert line["metrics"]["moe.overflow_share"]["value"] == 0.0
    # the other cells' own metrics stay off this line
    assert not {"lm_step.mfu", "train_step.mfu", "nemotron_step.mfu",
                "ssm.chunks_per_step"} & set(line["metrics"])


def test_entries_are_appended_and_list_the_cell():
    bench = read_json("BENCHMARK.json")
    by_name = {m["name"]: m for m in bench["per_layer"]}
    names = [m["name"] for m in bench["per_layer"]]
    assert [n for n in names if n in NEW] == NEW
    assert names.index(NEW[0]) > names.index("ssm.chunks_per_step")
    for name in NEW:
        assert by_name[name]["workloads"] == [CELL]
        assert load_file("benchmarks/metrics/%s.py" % name, "reader").read
        if name.endswith("_roofline") or name.endswith(".mfu"):
            assert by_name[name]["unit"] == "%"
    for name in SHARED:
        assert by_name[name]["workloads"][0] == "trinity_mini.fit"
        assert CELL in by_name[name]["workloads"]
    # the counts of operations that know afmoe's layers only stay its own
    # (the grouped products' count reads the routed layers' keys alone)
    for name in ("lm_step.mfu", "attn_roofline"):
        assert by_name[name]["workloads"] == ["trinity_mini.fit"]
    cell = {w["name"]: w for w in bench["workloads"]}[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "kimi_linear", "fit_lm.s8k", 1)
    entry = {c["name"]: c for c in bench["configs"]}["kimi_linear"]
    assert entry["file"] == "benchmarks/configs/kimi_linear.json"
    cfg = read_json(entry["file"])
    assert cfg["source"].startswith(entry["source"])
    assert "model_type kimi_linear" in entry["why"] + cfg["source"]
    assert entry["reduced"] == cfg["reduced"]
    # the cells that were there are as they were
    assert [w["name"] for w in bench["workloads"]][:3] == [
        "resnet50.fit", "trinity_mini.fit", "nemotron3_nano.fit"]


def test_control_and_planted_faults_are_not_correct(capsys):
    tool = load_file("benchmarks/tools/limits_faults.py", "limits_faults")
    tool.main(["--workload", CELL, "--seeds", "7", "--control-seeds", "7",
               "--rehearse"])
    row = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    sides = {p["side"]: p for p in row["proved"]}
    assert set(sides) == {
        "program", "control", "half_batch", "decay_per_head", "no_delta",
        "beta_one", "no_qk_norm", "gate_silu", "bf16_decay", "mla_scale",
        "no_k_pe", "no_kva_norm", "state_unchanged", "bn_stats_unchanged"}
    for side, p in sides.items():
        # float32 on the CPU: even a decay rounded to bfloat16 shows, after
        # the rehearsal's 32 tokens (a fast channel's decay is 0.2 a token)
        sound = side == "program"
        assert p["correct"] is sound, (side, p["numbers"])
        assert bool(p["over"]) is (not sound)
    assert sides["bn_stats_unchanged"]["over"] == ["bn_stats"]
