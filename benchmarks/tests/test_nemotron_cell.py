"""The hybrid state-space cell on the CPU: its rehearsal line carries
every metric that needs no device trace, its entries are appended and list
the cell, and ``correct`` comes out false for the control and for every
planted fault but the one only a long sequence shows."""
import json

from benchmarks.harness.files import load_file, read_json

CELL = "nemotron3_nano.fit"
NEW = ["nemotron_step.mfu", "ssm.ms", "ssm.scan_ms", "ssm_roofline",
       "ssm.chunks_per_step"]
#: the accepted metrics of the layers the cell shares with
#: ``trinity_mini.fit``: the cell is appended to their lists
SHARED = ["attn.ms", "attn.fwd_ms", "moe.ms", "moe.dispatch_ms",
          "moe.load_max_over_mean", "moe.chunks_per_pass",
          "moe.overflow_share"]


def test_rehearsal_line_carries_every_metric_without_a_device_trace(capsys):
    from benchmarks import run
    run.main(["--workload", CELL, "--seed", str(2 ** 31 + 34), "--seconds",
              "1", "--trace", "1", "--rehearse"])
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] is True and line["rehearsal"] is True
    bench = read_json("BENCHMARK.json")
    mine = [m for m in bench["per_layer"]
            if "workloads" not in m or CELL in m["workloads"]]
    want = {m["name"] for m in mine if m["source"] != "device_trace"
            and not m["name"].endswith(".mfu")}
    assert set(line["metrics"]) == want
    assert {"ssm.chunks_per_step", "moe.chunks_per_pass",
            "moe.load_max_over_mean", "moe.overflow_share",
            "fit.feed_ms"} <= want
    assert line["metrics"]["fit.dispatches_per_batch"]["value"] == 1.0
    assert line["metrics"]["compile.in_window"]["value"] == 0.0
    # 2 sequences of 32 tokens in chunks of 8, four mixers
    assert line["metrics"]["ssm.chunks_per_step"]["value"] == 2 * 4 * 4
    assert 1.0 <= line["metrics"]["moe.load_max_over_mean"]["value"] <= 4.0
    # every held expert's rows and a tile after them: one chunk
    assert line["metrics"]["moe.chunks_per_pass"]["value"] == 1.0
    assert line["metrics"]["moe.overflow_share"]["value"] == 0.0
    # the other cells' own metrics stay off this line
    assert not {"lm_step.mfu", "moe_gmm_roofline", "train_step.mfu"} \
        & set(line["metrics"])


def test_entries_are_appended_and_list_the_cell():
    bench = read_json("BENCHMARK.json")
    by_name = {m["name"]: m for m in bench["per_layer"]}
    names = [m["name"] for m in bench["per_layer"]]
    assert [n for n in names if n in NEW] == NEW
    assert names.index(NEW[0]) > names.index("attn.fwd_ms")
    for name in NEW:
        assert by_name[name]["workloads"] == [CELL]
        assert load_file("benchmarks/metrics/%s.py" % name, "reader").read
    for name in NEW:
        if name.endswith("_roofline") or name.endswith(".mfu"):
            assert by_name[name]["unit"] == "%"
    for name in SHARED:
        assert by_name[name]["workloads"] == ["trinity_mini.fit", CELL]
    # the counts of operations that know afmoe's layers only stay its own
    for name in ("lm_step.mfu", "attn_roofline", "moe_gmm_roofline"):
        assert by_name[name]["workloads"] == ["trinity_mini.fit"]
    cell = {w["name"]: w for w in bench["workloads"]}[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "nemotron3_nano", "fit_lm.s8k", 1)
    entry = {c["name"]: c for c in bench["configs"]}["nemotron3_nano"]
    assert entry["file"] == "benchmarks/configs/nemotron3_nano.json"
    cfg = read_json(entry["file"])
    # the entry's source is the catalog's URL itself; the family is named
    # in its why and in the file's own source
    assert cfg["source"].startswith(entry["source"])
    assert "model_type nemotron_h" in entry["why"] + cfg["source"]
    assert entry["reduced"] == cfg["reduced"]
    # the cells that were there are as they were
    assert [w["name"] for w in bench["workloads"]][:2] == [
        "resnet50.fit", "trinity_mini.fit"]


def test_control_and_planted_faults_are_not_correct(capsys):
    tool = load_file("benchmarks/tools/limits_faults.py", "limits_faults")
    tool.main(["--workload", CELL, "--seeds", "7", "--control-seeds", "7",
               "--rehearse"])
    row = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    sides = {p["side"]: p for p in row["proved"]}
    assert set(sides) == {"program", "control", "half_batch", "no_gate",
                          "decay_sign", "no_skip", "relu_not_squared",
                          "bf16_decay", "state_unchanged",
                          "bn_stats_unchanged"}
    for side, p in sides.items():
        # a slow head's decay rounding to 1 shows after hundreds of
        # tokens, not after the rehearsal's 32 (tests/test_nemotron_h.py)
        sound = side in ("program", "bf16_decay")
        assert p["correct"] is sound, (side, p["numbers"])
        assert bool(p["over"]) is (not sound)
    assert sides["bn_stats_unchanged"]["over"] == ["bn_stats"]
