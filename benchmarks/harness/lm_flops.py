"""Operations and bytes a decoder language model's training step requires,
counted from its configuration (``windows/fit_lm.model_config``).

The count is the algorithm's, as in ``flops.py``: one multiply-add is 2
FLOPs, a training step is 3 x forward. Attention counts only the
query-key pairs the mask leaves; the routed experts count the rows
routed to the experts held here at a uniform load (tokens x top-k x held /
experts), whatever the router did in a given step and whatever implements
the product. Nothing that an implementation recomputes, pads or gathers
changes it.
"""


def attended_pairs(seq_len, window=0):
    """Query-key pairs one head attends in a causal sequence: position i
    sees j <= i, with ``window`` only 0 <= i - j < window."""
    w = min(window, seq_len) if window else seq_len
    # the first w positions see 1..w keys, the rest w each
    return w * (w + 1) // 2 + (seq_len - w) * w


def layer_kinds(model):
    """``[(sliding, dense)]`` of the layers kept."""
    return [(kind == "sliding_attention", i < model["num_dense_layers"])
            for i, kind in enumerate(
                model["layer_types"][:model["num_hidden_layers"]])]


def routed_rows(model, tokens):
    """Rows the held experts take in one layer at a uniform load."""
    held = model["experts_held"][1]
    return tokens * model["num_experts_per_tok"] * held / model["num_experts"]


def forward_macs(model, sequences, seq_len):
    """Multiply-adds of one forward pass over ``sequences`` sequences of
    ``seq_len`` tokens, by part."""
    d, dim = model["hidden_size"], model["head_dim"]
    hq, hkv = model["num_attention_heads"], model["num_key_value_heads"]
    f, fd = model["moe_intermediate_size"], model["intermediate_size"]
    tokens = sequences * seq_len
    parts = dict.fromkeys(("projections", "attention", "dense_ffn",
                           "shared_expert", "router", "routed_experts",
                           "head"), 0)
    for sliding, dense in layer_kinds(model):
        # q, output gate and output: d x heads*dim each; k, v: d x kv*dim
        parts["projections"] += tokens * d * dim * (3 * hq + 2 * hkv)
        pairs = attended_pairs(seq_len,
                               model["sliding_window"] if sliding else 0)
        parts["attention"] += sequences * pairs * hq * dim * 2   # QK^T, PV
        if dense:
            parts["dense_ffn"] += tokens * 3 * d * fd
        else:
            parts["shared_expert"] += tokens * 3 * d * f \
                * model["num_shared_experts"]
            parts["router"] += tokens * d * model["num_experts"]
            parts["routed_experts"] += routed_rows(model, tokens) * 3 * d * f
    parts["head"] = tokens * d * model["vocab_size"]
    return parts


def train_step_flops(model, sequences, seq_len):
    """FLOPs one training step requires: 3 x forward, 2 per multiply-add."""
    return 3 * 2 * sum(forward_macs(model, sequences, seq_len).values())


def _least(passes, macs, bytes_, peak_flops, peak_bytes_per_s):
    t_flops, t_bytes = 2 * macs / peak_flops, bytes_ / peak_bytes_per_s
    return (passes * max(t_flops, t_bytes), passes * t_flops,
            passes * t_bytes)


def attention_least_seconds(model, sequences, seq_len, peak_flops,
                            peak_bytes_per_s, act_bytes=2):
    """The least time the chip could take for the step's attention
    products (scores and values of the unmasked pairs): per layer three
    passes (forward, dQ, dK and dV), each the larger of FLOPs / peak and
    the bytes of q, k, v and o / bandwidth. ``(seconds, if compute-bound,
    if memory-bound)``."""
    dim = model["head_dim"]
    hq, hkv = model["num_attention_heads"], model["num_key_value_heads"]
    total = [0.0, 0.0, 0.0]
    for sliding, _ in layer_kinds(model):
        pairs = attended_pairs(seq_len,
                               model["sliding_window"] if sliding else 0)
        macs = sequences * pairs * hq * dim * 2
        bytes_ = act_bytes * sequences * seq_len * dim * (2 * hq + 2 * hkv)
        for i, t in enumerate(_least(3, macs, bytes_, peak_flops,
                                     peak_bytes_per_s)):
            total[i] += t
    return tuple(total)


def grouped_least_seconds(model, tokens, peak_flops, peak_bytes_per_s,
                          act_bytes=2, weight_bytes=2):
    """The least time for the routed rows' three products (``x U1``, ``x
    U3``, ``h U2`` over rows sorted by expert), per expert layer three
    passes, each the larger of FLOPs / peak and bytes / bandwidth, where a
    pass moves the rows' input, hidden and output and the held experts'
    weights once."""
    d, f = model["hidden_size"], model["moe_intermediate_size"]
    held = model["experts_held"][1]
    rows = routed_rows(model, tokens)
    total = [0.0, 0.0, 0.0]
    for _, dense in layer_kinds(model):
        if dense:
            continue
        macs = rows * 3 * d * f
        bytes_ = act_bytes * rows * (2 * d + 3 * f) \
            + weight_bytes * held * 3 * d * f
        for i, t in enumerate(_least(3, macs, bytes_, peak_flops,
                                     peak_bytes_per_s)):
            total[i] += t
    return tuple(total)
