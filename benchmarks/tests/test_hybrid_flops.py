"""``harness/hybrid_flops.py`` against counts worked out by hand, at one
small shape and at the cell's, and the configuration's file against
itself."""
from benchmarks.harness import hybrid_flops, lm_flops
from benchmarks.harness.files import load_file, read_json

SMALL = dict(
    hidden_size=8, num_attention_heads=4, num_key_value_heads=2, head_dim=4,
    layer_types=["mamba", "moe", "attention", "mamba"], num_hidden_layers=4,
    mamba_num_heads=2, mamba_head_dim=3, ssm_state_size=5, n_groups=1,
    chunk_size=4, num_experts=8, experts_held=[0, 2], num_experts_per_tok=2,
    moe_intermediate_size=6, num_shared_experts=1,
    moe_shared_expert_intermediate_size=7, vocab_size=11)


def _cell():
    cfg = read_json("benchmarks", "configs", "nemotron3_nano.json")
    window = load_file("benchmarks/windows/fit_lm.py", "fit_lm")
    lm_model = window.model_config(cfg, False)
    return cfg, hybrid_flops.model_of(cfg, lm_model), window


def test_count_by_hand_at_a_small_shape():
    """2 sequences of 8 tokens: 16 tokens."""
    parts = hybrid_flops.forward_macs(SMALL, 2, 8)
    assert hybrid_flops.blocks(SMALL) == {"mamba": 2, "moe": 1,
                                          "attention": 1}
    # in: 8 x (z 6 + x 6 + B 5 + C 5 + dt 2); out: 6 x 8
    assert parts["mamba_projections"] == 2 * 16 * (8 * 24 + 6 * 8)
    # a token: C B^T 1 group x 4 x 5; its row of the chunk against delta x
    # 2 heads x 4 x 3; its outer product into the state and its read of
    # the entering state 2 x 3 x 5 each
    assert hybrid_flops.ssd_macs_per_token(SMALL) == 20 + 24 + 30 + 30
    assert parts["ssd"] == 2 * 16 * 104
    assert parts["attention_projections"] == 16 * 8 * 4 * (2 * 4 + 2 * 2)
    # 8 positions see 1..8 keys: 36 pairs a head, scores and values
    assert parts["attention"] == 2 * 36 * 4 * 4 * 2
    assert parts["shared_expert"] == 16 * 2 * 8 * 7
    assert parts["router"] == 16 * 8 * 8
    # 16 x 2 selections, 2 of 8 experts held: 8 rows
    assert parts["routed_experts"] == 8 * 2 * 8 * 6
    assert parts["head"] == 16 * 8 * 11
    assert hybrid_flops.train_step_flops(SMALL, 2, 8) \
        == 6 * sum(parts.values())


def test_forward_macs_of_the_cell():
    """ISSUE 34's count: 358.8 M multiply-adds a token, 17.6 TFLOP a
    step."""
    _, model, _ = _cell()
    per = {k: v / 8192 / 1e6
           for k, v in hybrid_flops.forward_macs(model, 1, 8192).items()}
    want = {"mamba_projections": 154.8, "ssd": 6.8,
            "attention_projections": 23.4, "attention": 33.6,
            "shared_expert": 79.8, "routed_experts": 15.0, "router": 1.4,
            "head": 44.0}
    assert {k: round(v, 1) for k, v in per.items()} == want
    assert round(sum(per.values()), 1) == 358.8
    assert round(hybrid_flops.train_step_flops(model, 1, 8192) / 1e12,
                 1) == 17.6
    assert lm_flops.routed_rows(model, 8192) == 3072      # 384 an expert


def test_ssd_least_time_is_bound_by_its_bytes():
    _, model, _ = _cell()
    t, by_flops, by_bytes = hybrid_flops.ssd_least_seconds(
        model, 1, 8192, 197e12, 819e9)
    assert t == by_bytes > by_flops > 0
    # 4 mixers x 3 passes x 2 bytes x 8,192 x (2 x 4,096 + 2 x 1,024 + 64)
    assert abs(by_bytes - 12 * 2 * 8192 * 10304 / 819e9) < 1e-12
    assert abs(by_flops - 12 * 2 * 8192 * 1703936 / 197e12) < 1e-12


def test_the_file_counts_its_own_parameters():
    cfg, model, window = _cell()
    sym = window.build_symbol(cfg, window.model_config(cfg, False), False)
    _, params, aux, nodes, _ = window.shapes_of(
        sym, cfg, {"data": "int32", "label": "float32"}, (1, 8192))
    total = 0
    for shape, _ in params.values():
        n = 1
        for s in shape:
            n *= s
        total += n
    assert total == cfg["parameters"] == 666962944
    assert cfg["hybrid_override_pattern"] == "MEMEM*EME"
    assert model["layer_types"] == [
        "mamba", "moe", "mamba", "moe", "mamba", "attention", "moe", "mamba",
        "moe"]
    # every published width as the catalog has it
    for k, v in dict(hidden_size=2688, head_dim=128, mamba_num_heads=64,
                     mamba_head_dim=64, ssm_state_size=128, n_groups=8,
                     chunk_size=128, conv_kernel=4, moe_intermediate_size=1856,
                     moe_shared_expert_intermediate_size=3712,
                     num_experts_per_tok=6, num_attention_heads=32,
                     num_key_value_heads=2, intermediate_size=1856,
                     expand=2).items():
        assert cfg[k] == v, k
    assert (cfg["router_experts"], cfg["num_experts"],
            cfg["n_routed_experts"], cfg["vocab_size"]) == (128, 8, 8, 16384)
    assert sorted(cfg["reduced"]) == sorted(
        ["num_hidden_layers", "hybrid_override_pattern", "n_routed_experts",
         "vocab_size"])
