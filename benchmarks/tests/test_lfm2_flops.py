"""``harness/lfm2_flops.py`` against counts worked out by hand, at one small
shape and at the cell's, and the configuration's file against the
catalog's row and its own counts of parameters."""
from benchmarks.harness import lfm2_flops as flops
from benchmarks.harness.files import load_file, read_json

SMALL = dict(
    hidden_size=8, layer_types=["conv", "full_attention", "conv"],
    num_hidden_layers=3, num_dense_layers=1, intermediate_size=10,
    head_dim=2, num_attention_heads=4, num_key_value_heads=2, conv_L_cache=3,
    num_experts=8, experts_held=[0, 2], num_experts_per_tok=2,
    moe_intermediate_size=6, vocab_size=11)

#: the catalog's row (LFM2-8B-A1B's config.json), every number in it
ROW = dict(conv_L_cache=3, conv_bias=False, hidden_size=2048,
           intermediate_size=7168, max_position_embeddings=128000,
           model_type="lfm2_moe", moe_intermediate_size=1792, norm_eps=1e-5,
           norm_topk_prob=True, num_attention_heads=32, num_dense_layers=2,
           num_experts=32, num_experts_per_tok=4, num_hidden_layers=24,
           num_key_value_heads=8, rope_theta=1000000, routed_scaling_factor=1,
           use_expert_bias=True, vocab_size=65536)
ATTENTION_AT = (2, 6, 10, 14, 18, 21)


def _cell():
    cfg = read_json("benchmarks", "configs", "lfm2_moe.json")
    window = load_file("benchmarks/windows/fit_lm.py", "fit_lm")
    return cfg, flops.model_of(cfg, window.model_config(cfg, False))


def test_count_by_hand_at_a_small_shape():
    """2 sequences of 8 tokens: 16 tokens."""
    parts = flops.forward_macs(SMALL, 2, 8)
    assert flops.layers(SMALL) == {"conv": 2, "full_attention": 1}
    # in 8 x 24 and out 8 x 8 a conv layer
    assert parts["conv_projections"] == 2 * 16 * (8 * 24 + 8 * 8)
    # q and o 8 x 4 x 2 each, k and v 8 x 2 x 2 each
    assert parts["attention_projections"] == 16 * (2 * 64 + 2 * 32)
    # 8 positions see 1..8 keys: 36 pairs a head; scores and values at 2
    assert parts["attention"] == 2 * 36 * 4 * 2 * 2
    assert parts["dense_ffn"] == 16 * 3 * 8 * 10
    assert parts["router"] == 2 * 16 * 8 * 8
    # 16 x 2 selections, 2 of 8 experts held: 8 rows a layer
    assert parts["routed_experts"] == 2 * 8 * 3 * 8 * 6
    assert parts["head"] == 16 * 8 * 11
    assert flops.train_step_flops(SMALL, 2, 8) == 6 * sum(parts.values())
    # a conv layer moves 16 x (24 + 8) values of 2 bytes a pass
    t = flops.shortconv_least_seconds(SMALL, 2, 8, 1e12, 1e9)
    want = (2 * 3 * 16 * 32 * 2 / 1e9, 2 * 3 * 2 * 16 * 8 * 5 / 1e12,
            2 * 3 * 16 * 32 * 2 / 1e9)
    assert all(abs(a - b) <= 1e-12 * b for a, b in zip(t, want))


def test_forward_macs_of_the_cell():
    """The count by part worked out by hand: 277.1 M multiply-adds a
    token, 13.62 TFLOP a step; the short convolutions' least time 0.49 ms
    a layer, 2.46 ms a step."""
    _, model = _cell()
    per = {k: v / 8192 / 1e6
           for k, v in flops.forward_macs(model, 1, 8192).items()}
    want = {"conv_projections": 83.9, "attention_projections": 10.5,
            "attention": 16.8, "dense_ffn": 88.1, "router": 0.3,
            "routed_experts": 44.0, "head": 33.6}
    assert {k: round(v, 1) for k, v in per.items()} == want
    assert round(sum(per.values()), 1) == 277.1
    assert round(flops.train_step_flops(model, 1, 8192) / 1e12, 2) == 13.62
    least = flops.shortconv_least_seconds(model, 1, 8192, 197e12, 819e9)
    assert least[0] == least[2] and round(least[0] * 1e3, 2) == 2.46


def _count(c, experts, vocab, tied=True):
    """Parameters of ``c``'s layers with ``experts`` held a layer and
    ``vocab`` rows, by hand."""
    d, dim = c["hidden_size"], 64
    conv = 3 * d * d + 3 * d + d * d
    attn = d * 32 * dim * 2 + d * 8 * dim * 2 + 2 * dim
    dense = 3 * d * c["intermediate_size"]
    expert_layer = experts * 3 * d * c["moe_intermediate_size"] + 32 * d
    total = d + (1 if tied else 2) * vocab * d       # the final norm
    for i, kind in enumerate(c["layer_types"][:c["num_hidden_layers"]]):
        total += 2 * d + (conv if kind == "conv" else attn)
        total += dense if i < c["num_dense_layers"] else expert_layer
    return total


def test_the_file_keeps_the_catalog_row_and_its_counts():
    cfg, model = _cell()
    reduced = set(cfg["reduced"])
    assert reduced == {"num_hidden_layers", "layer_types", "num_experts",
                       "vocab_size"}
    for k, v in ROW.items():
        if k not in reduced:
            assert cfg[k] == v and type(cfg[k]) is type(v), k
    assert cfg["published"]["num_hidden_layers"] == 24
    assert cfg["published"]["vocab_size"] == 65536
    assert (cfg["num_experts"], cfg["router_experts"]) == (8, 32)
    assert (cfg["num_hidden_layers"], cfg["vocab_size"]) == (6, 16384)
    published = ["full_attention" if i in ATTENTION_AT else "conv"
                 for i in range(24)]
    assert cfg["layer_types"] == published[:6]
    assert model["experts_held"] == [0, 8] and model["num_experts"] == 32
    assert cfg["parameters"] == _count(cfg, 8, 16384) == 568647808
    whole = dict(cfg, layer_types=published, num_hidden_layers=24)
    assert _count(whole, 32, 65536) == 8339929856
    assert _count(whole, 32, 65536, tied=False) == 8474147584
    # two chips a layer: half of the experts and of the vocabulary
    assert _count(cfg, 16, 32768) == 954523776
    assert round(cfg["parameters"] * 14 / 1e9, 2) == 7.96


def test_the_accepted_grouped_count_reads_the_cell():
    """``moe_gmm_roofline``'s count (``lm_flops.grouped_least_seconds``)
    over this cell's model: four expert layers (the two dense ones left
    out), 8,192 rows a layer at a uniform load (8,192 x 4 x 8 / 32), three
    products of 2,048 x 1,792 a row, the eight held experts' weights."""
    from benchmarks.harness import lm_flops
    _, model = _cell()
    rows, d, f = 8192 * 4 * 8 / 32, 2048, 1792
    macs = rows * 3 * d * f
    bytes_ = 2 * rows * (2 * d + 3 * f) + 2 * 8 * 3 * d * f
    one = lm_flops._least(3, macs, bytes_, 197e12, 819e9)
    got = lm_flops.grouped_least_seconds(model, 8192, 197e12, 819e9)
    assert all(abs(g - 4 * o) <= 1e-12 * o for g, o in zip(got, one))
    assert got[0] == got[1]         # bound by its FLOPs
