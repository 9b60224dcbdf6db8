"""The LFM2 cell on the CPU: its rehearsal line carries every metric that
needs no device trace, its entries are appended and list the cell, and
``correct`` comes out false for the control and for the planted faults
that can show from the window's starting point."""
import json

from benchmarks.harness.files import load_file, read_json

CELL = "lfm2_moe.fit"
NEW = ["lfm2_step.mfu", "shortconv.ms", "shortconv_roofline",
       "shortconv.layers_per_step", "lfm2_attn_roofline"]
#: the accepted metrics of the layers the cell shares with the language
#: cells that were there: the cell is appended to their lists
SHARED = ["attn.ms", "attn.fwd_ms", "moe.ms", "moe.dispatch_ms",
          "moe.load_max_over_mean", "moe.chunks_per_pass",
          "moe.overflow_share", "moe_gmm_roofline"]


def test_rehearsal_line_carries_every_metric_without_a_device_trace(capsys):
    from benchmarks import run
    run.main(["--workload", CELL, "--seed", str(2 ** 31 + 40), "--seconds",
              "1", "--trace", "1", "--rehearse"])
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] is True and line["rehearsal"] is True
    bench = read_json("BENCHMARK.json")
    mine = [m for m in bench["per_layer"]
            if "workloads" not in m or CELL in m["workloads"]]
    want = {m["name"] for m in mine if m["source"] != "device_trace"
            and not m["name"].endswith(".mfu")}
    assert set(line["metrics"]) == want
    # five of the six layers are short-convolution mixers
    assert line["metrics"]["shortconv.layers_per_step"]["value"] == 5.0
    assert line["metrics"]["fit.dispatches_per_batch"]["value"] == 1.0
    assert line["metrics"]["compile.in_window"]["value"] == 0.0
    assert not {"lm_step.mfu", "kimi_step.mfu", "nemotron_step.mfu",
                "kda.chunks_per_step", "ssm.chunks_per_step"} \
        & set(line["metrics"])


def test_entries_are_appended_and_list_the_cell():
    bench = read_json("BENCHMARK.json")
    by_name = {m["name"]: m for m in bench["per_layer"]}
    names = [m["name"] for m in bench["per_layer"]]
    assert [n for n in names if n in NEW] == NEW
    assert min(names.index(n) for n in NEW) > names.index("setup.cache_misses")
    for name in NEW:
        assert by_name[name]["workloads"] == [CELL]
        assert by_name[name]["moves"] == "train_img_s"
        assert load_file("benchmarks/metrics/%s.py" % name, "reader").read
        if name.endswith("_roofline") or name.endswith(".mfu"):
            assert by_name[name]["unit"] == "%"
    for name in SHARED:
        assert CELL in by_name[name]["workloads"]
    # the counts of operations that know afmoe's layers only stay its own
    for name in ("lm_step.mfu", "attn_roofline"):
        assert CELL not in by_name[name]["workloads"]
    cell = {w["name"]: w for w in bench["workloads"]}[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "lfm2_moe", "fit_lm.s8k", 1)
    entry = {c["name"]: c for c in bench["configs"]}["lfm2_moe"]
    assert entry["file"] == "benchmarks/configs/lfm2_moe.json"
    cfg = read_json(entry["file"])
    assert cfg["source"].startswith(entry["source"])
    assert entry["reduced"] == cfg["reduced"]


def test_control_and_planted_faults_are_not_correct(capsys):
    """Every side but ``biased_weights``: the window starts the selection
    bias at nought, so for the first step the biased and the unbiased
    scores are one, and after it they differ by the update's 1e-3."""
    tool = load_file("benchmarks/tools/limits_faults.py", "limits_faults")
    ref = load_file("benchmarks/reference/lfm2_moe.py", "lfm2_reference")
    sides = ["control", "half_batch", "state_unchanged", "bn_stats_unchanged"] \
        + [f for f in ref.FAULTS if f != "biased_weights"]
    tool.main(["--workload", CELL, "--seeds", "7", "--control-seeds", "7",
               "--sides", ",".join(sides), "--rehearse"])
    row = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    read = {p["side"]: p for p in row["proved"]}
    assert set(read) == set(sides) | {"program"}
    for side, p in read.items():
        sound = side == "program"
        assert p["correct"] is sound, (side, p["numbers"])
        assert bool(p["over"]) is (not sound)
    assert read["bn_stats_unchanged"]["over"] == ["bn_stats"]
