"""``harness/kimi_linear_flops.py`` against counts worked out by hand, at
one small shape and at the cell's, and the configuration's file against
itself and the catalog's row."""
from benchmarks.harness import kimi_linear_flops as flops, lm_flops
from benchmarks.harness.files import load_file, read_json

SMALL = dict(
    hidden_size=8, layer_types=["kda", "mla", "kda"], num_hidden_layers=3,
    num_dense_layers=1, intermediate_size=10, kda_num_heads=2, kda_head_dim=4,
    kda_chunk=4, num_attention_heads=2, kv_lora_rank=5, qk_nope_head_dim=4,
    qk_rope_head_dim=2, v_head_dim=3, num_experts=8, experts_held=[0, 2],
    num_experts_per_tok=2, moe_intermediate_size=6, num_shared_experts=1,
    vocab_size=11)


def _cell():
    cfg = read_json("benchmarks", "configs", "kimi_linear.json")
    window = load_file("benchmarks/windows/fit_lm.py", "fit_lm")
    lm_model = window.model_config(cfg, False)
    return cfg, flops.model_of(cfg, lm_model), window


def test_count_by_hand_at_a_small_shape():
    """2 sequences of 8 tokens: 16 tokens."""
    parts = flops.forward_macs(SMALL, 2, 8)
    assert flops.layers(SMALL) == {"kda": 2, "mla": 1}
    # q, k, v, out 8 x 8 each; two waists 8 x 4 + 4 x 8; beta 8 x 2
    assert parts["kda_projections"] == 2 * 16 * (4 * 64 + 2 * 64 + 16)
    # a head and a position: the two blocks 2 x 4 x 4; the inverse of a
    # 4 x 4 block 2 doublings-products of 4 x 4 a row; W and U 2 x 4 x 4;
    # three products with the 4 x 4 state and Aqk U~ 4 x 4
    assert flops.kda_macs_per_token(SMALL) == 2 * (32 + 32 + 32 + 48 + 16)
    assert parts["kda"] == 2 * 16 * 320
    # q 8 x 2 x 6; [c | k_pe] 8 x 7; [k_nope | v] 5 x 2 x 7; out 6 x 8
    assert parts["mla_projections"] == 16 * (96 + 56 + 70 + 48)
    # 8 positions see 1..8 keys: 36 pairs a head; scores at 6, values at 3
    assert parts["mla_attention"] == 2 * 36 * 2 * 9
    assert parts["dense_ffn"] == 16 * 3 * 8 * 10
    assert parts["shared_expert"] == 2 * 16 * 3 * 8 * 6
    assert parts["router"] == 2 * 16 * 8 * 8
    # 16 x 2 selections, 2 of 8 experts held: 8 rows a layer
    assert parts["routed_experts"] == 2 * 8 * 3 * 8 * 6
    assert parts["head"] == 16 * 8 * 11
    assert flops.train_step_flops(SMALL, 2, 8) == 6 * sum(parts.values())


def test_forward_macs_of_the_cell():
    """ISSUE 36's count by part (393 M multiply-adds a token, 19.3 TFLOP a
    step), but for the recurrence: the inverse by doubling is counted at
    the ten products of 64 x 64 x 64 it takes (five squarings, five
    products), where the issue reckoned six: 16.8 M a token for its 15."""
    _, model, _ = _cell()
    per = {k: v / 8192 / 1e6
           for k, v in flops.forward_macs(model, 1, 8192).items()}
    want = {"kda_projections": 157.8, "kda": 16.8, "mla_projections": 29.1,
            "mla_attention": 41.9, "dense_ffn": 63.7, "shared_expert": 28.3,
            "router": 2.4, "routed_experts": 7.1, "head": 47.2}
    assert {k: round(v, 1) for k, v in per.items()} == want
    assert round(sum(per.values()), 1) == 394.3
    assert round(flops.train_step_flops(model, 1, 8192) / 1e12, 1) == 19.4
    assert lm_flops.routed_rows(model, 8192) == 2048      # 256 an expert
    assert flops.kda_macs_per_token(model) == 32 * (
        2 * 64 * 128 + 10 * 64 * 64 + 2 * 64 * 128 + 3 * 128 * 128
        + 64 * 128)


def test_least_times():
    """The recurrence is bound by its bytes, the attention by its
    FLOPs."""
    _, model, _ = _cell()
    t, by_flops, by_bytes = flops.kda_least_seconds(model, 1, 8192, 197e12,
                                                    819e9)
    assert t == by_bytes > by_flops > 0
    # 4 layers x 3 passes x 2 bytes x 8,192 x (5 x 4,096 + 32)
    assert abs(by_bytes - 12 * 2 * 8192 * 20512 / 819e9) < 1e-12
    assert abs(by_flops - 12 * 2 * 8192 * 4194304 / 197e12) < 1e-12
    t, by_flops, by_bytes = flops.mla_attention_least_seconds(
        model, 1, 8192, 197e12, 819e9)
    assert t == by_flops > by_bytes > 0
    pairs = 8192 * 8193 // 2
    assert abs(by_flops - 3 * 2 * pairs * 32 * 320 / 197e12) < 1e-12
    assert abs(by_bytes - 3 * 2 * 8192 * 32 * 640 / 819e9) < 1e-12


def test_the_file_counts_its_own_parameters():
    cfg, model, window = _cell()
    sym = window.build_symbol(cfg, window.model_config(cfg, False), False)
    _, params, aux, nodes, _ = window.shapes_of(
        sym, cfg, {"data": "int32", "label": "float32"}, (1, 8192))

    def size(shape):
        n = 1
        for s in shape:
            n *= s
        return n

    total = sum(size(shape) for shape, _ in params.values())
    assert total == cfg["parameters"] == 602433408
    # ISSUE 36's 602,434,432 counts the four selection biases of 256 too
    bias = sum(size(s) for k, s in aux.items() if k.endswith("_moe_bias"))
    assert bias == 4 * 256 and total + bias == 602434432
    by_layer = {}
    for k, (shape, _) in params.items():
        if k.startswith("l0_kda_") or (k.startswith("l3_attn_")
                                       and k != "l3_attn_norm_gamma"):
            by_layer[k[:3]] = by_layer.get(k[:3], 0) + size(shape)
    assert by_layer == {"l0_": 39514272, "l3_": 29114880}
    assert model["layer_types"] == ["kda", "kda", "kda", "mla", "kda"]


def test_every_published_number_is_the_catalogs():
    """Every top-level number of the catalog's row (copied here: the
    catalog is not in the repository) but the three reduced; the nested
    group whole but for its two layer lists."""
    cfg = read_json("benchmarks", "configs", "kimi_linear.json")
    published = dict(
        first_k_dense_replace=1, head_dim=72, hidden_size=2304,
        intermediate_size=9216, kv_lora_rank=512, model_max_length=1048576,
        moe_intermediate_size=1024, moe_layer_freq=1, num_attention_heads=32,
        num_expert_group=1, num_experts=256, num_experts_per_token=8,
        num_hidden_layers=27, num_key_value_heads=32,
        num_nextn_predict_layers=0, num_shared_experts=1,
        qk_nope_head_dim=128, qk_rope_head_dim=64, rms_norm_eps=1e-05,
        rope_theta=10000, routed_scaling_factor=2.446, topk_group=1,
        v_head_dim=128, vocab_size=163840)
    reduced = {"num_hidden_layers": 5, "num_experts": 8, "vocab_size": 20480}
    for k, v in published.items():
        assert cfg[k] == reduced.get(k, v), k
    assert (cfg["mla_use_nope"], cfg["moe_renormalize"],
            cfg["use_grouped_topk"], cfg["q_lora_rank"],
            cfg["moe_router_activation_func"], cfg["model_type"]) == (
        True, True, True, None, "sigmoid", "kimi_linear")
    assert cfg["linear_attn_config"] == {
        "full_attn_layers": [4], "head_dim": 128, "kda_layers": [1, 2, 3, 5],
        "num_heads": 32, "short_conv_kernel_size": 4}
    assert sorted(cfg["reduced"]) == sorted(
        ["num_hidden_layers", "linear_attn_config", "num_experts",
         "vocab_size"])
    assert (cfg["router_experts"], cfg["num_experts_per_tok"],
            cfg["route_scale"]) == (256, 8, 2.446)
    kw = cfg["symbol"]["kwargs"]
    assert (kw["kda_num_heads"], kw["kda_head_dim"],
            kw["short_conv_kernel_size"], kw["kda_chunk"]) == (32, 128, 4, 64)
    assert "32 chips" in cfg["deployment"] and cfg["published"][
        "num_hidden_layers"] == 27
