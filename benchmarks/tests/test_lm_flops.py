"""``harness/lm_flops.py`` against counts worked out by hand, and the new
configuration's file against itself."""
import json
import os

from benchmarks.harness import lm_flops
from benchmarks.harness.files import load_file, read_json

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _model():
    cfg = read_json("benchmarks", "configs", "trinity_mini.json")
    window = load_file("benchmarks/windows/fit_lm.py", "fit_lm")
    return cfg, window.model_config(cfg, False), window


def test_attended_pairs():
    # every position sees itself and all before it
    assert lm_flops.attended_pairs(4) == 1 + 2 + 3 + 4
    # window 2, the position itself counted: 1, 2, 2, 2
    assert lm_flops.attended_pairs(4, 2) == 7
    assert lm_flops.attended_pairs(4, 9) == lm_flops.attended_pairs(4)
    brute = sum(1 for i in range(8192) for j in range(max(0, i - 2047),
                                                      i + 1))
    assert lm_flops.attended_pairs(8192, 2048) == brute
    # 1,792 keys a query on average under the window, 4,096 without
    assert round(brute / 8192) == 1792
    assert round(lm_flops.attended_pairs(8192) / 8192) == 4096


def test_forward_macs_of_the_cell():
    """ISSUE 27's count by hand: 369 M multiply-adds a token."""
    _, model, _ = _model()
    parts = lm_flops.forward_macs(model, 1, 8192)
    per_token = {k: v / 8192 for k, v in parts.items()}
    d = 2048
    assert per_token["projections"] == 5 * d * 128 * (3 * 32 + 2 * 4)
    assert per_token["dense_ffn"] == 3 * d * 6144
    assert per_token["shared_expert"] == 4 * 3 * d * 1024
    assert per_token["router"] == 4 * d * 128
    # 8 selections of 128 experts, 16 held: one expert's worth a token
    assert per_token["routed_experts"] == 4 * 3 * d * 1024
    assert per_token["head"] == d * 25024
    sliding = lm_flops.attended_pairs(8192, 2048) * 32 * 128 * 2 / 8192
    full = lm_flops.attended_pairs(8192) * 32 * 128 * 2 / 8192
    assert per_token["attention"] == 4 * sliding + full
    assert round(sum(per_token.values()) / 1e6) == 369
    assert lm_flops.train_step_flops(model, 1, 8192) \
        == 6 * sum(parts.values())
    assert lm_flops.routed_rows(model, 8192) == 8192     # 512 an expert


def test_least_seconds_bounds():
    _, model, _ = _model()
    peak, bw = 197e12, 819e9
    t, by_flops, by_bytes = lm_flops.attention_least_seconds(
        model, 1, 8192, peak, bw)
    assert t == by_flops > by_bytes > 0       # compute-bound at 8k
    macs = lm_flops.forward_macs(model, 1, 8192)["attention"]
    assert abs(t - 3 * 2 * macs / peak) < 1e-12
    t, by_flops, by_bytes = lm_flops.grouped_least_seconds(
        model, 8192, peak, bw)
    assert t >= max(by_flops, by_bytes) - 1e-12 and by_bytes > 0
    macs = lm_flops.forward_macs(model, 1, 8192)["routed_experts"]
    assert abs(by_flops - 3 * 2 * macs / peak) < 1e-12


def test_configuration_file_agrees_with_itself():
    """The catalog's numbers at the top level, the cut in ``reduced``, and
    the reference's copy of the model equal to what the window builds."""
    cfg, model, window = _model()
    assert cfg["reference"]["kwargs"]["config"] == model
    small = window.model_config(cfg, True)
    assert cfg["reference"]["kwargs"]["small"] == cfg["rehearse"]["model"]
    assert small["hidden_size"] == 64 != model["hidden_size"]
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        rows = [json.loads(line) for line in f]
    row = next(r for r in rows if r["name"] == "Trinity-Mini")
    changed = {k for k, v in row["config"].items() if cfg.get(k) != v}
    assert changed == set(cfg["reduced"])
    bench = read_json("BENCHMARK.json")
    entry = next(c for c in bench["configs"] if c["name"] == "trinity_mini")
    assert entry["reduced"] == cfg["reduced"]
    assert entry["source"] == row["source_url"]
    assert model["experts_held"] == [0, 16] and model["num_experts"] == 128
    assert len(model["layer_types"]) == model["num_hidden_layers"] == 5
