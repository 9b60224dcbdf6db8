"""Operations and bytes an ``lfm2_moe`` decoder's training step requires
(gated short-convolution mixers, grouped-query attention, leading dense
SwiGLU layers, sigmoid-routed SwiGLU experts without a shared one, a head
tied to the embedding), counted from its configuration.

As ``lm_flops.py``: the algorithm's count, one multiply-add is 2 FLOPs, a
training step is 3 x forward; attention counts the query-key pairs the
causal mask leaves; the routed experts count the rows routed to the
experts held here at a uniform load. Elementwise work (the short
convolution's gates and taps, norms, rotations) is not counted in the
step, here as there; the short convolution's own least time is its
bytes.
"""
from benchmarks.harness import lm_flops
from benchmarks.harness.hybrid_flops import model_of     # noqa: F401 -- the
# window's model over the symbol's keyword arguments, which carry the
# convolution's width

KINDS = ("conv", "full_attention")


def layers(model):
    """How many layers of each mixer are kept."""
    kept = list(model["layer_types"])[:model["num_hidden_layers"]]
    return {k: kept.count(k) for k in KINDS}


def forward_macs(model, sequences, seq_len):
    """Multiply-adds of one forward pass over ``sequences`` sequences of
    ``seq_len`` tokens, by part."""
    d, dim = model["hidden_size"], model["head_dim"]
    hq, hkv = model["num_attention_heads"], model["num_key_value_heads"]
    tokens = sequences * seq_len
    n = layers(model)
    kept = n["conv"] + n["full_attention"]
    dense = min(model["num_dense_layers"], kept)
    return {
        # in: d x 3 d (B, C, x); out: d x d
        "conv_projections": n["conv"] * tokens * 4 * d * d,
        # q and output: d x heads*dim each; k, v: d x kv*dim
        "attention_projections": n["full_attention"] * tokens * d * dim
        * (2 * hq + 2 * hkv),
        "attention": n["full_attention"] * sequences
        * lm_flops.attended_pairs(seq_len) * hq * dim * 2,      # QK^T, PV
        "dense_ffn": dense * tokens * 3 * d * model["intermediate_size"],
        "router": (kept - dense) * tokens * d * model["num_experts"],
        "routed_experts": (kept - dense)
        * lm_flops.routed_rows(model, tokens) * 3 * d
        * model["moe_intermediate_size"],
        "head": tokens * d * model["vocab_size"],
    }


def train_step_flops(model, sequences, seq_len):
    """FLOPs one training step requires: 3 x forward, 2 per multiply-add."""
    return 3 * 2 * sum(forward_macs(model, sequences, seq_len).values())


def shortconv_least_seconds(model, sequences, seq_len, peak_flops,
                            peak_bytes_per_s, act_bytes=2):
    """The least time the chip could take for the step's gated short
    convolutions (``C * conv(B * x)`` between the two projections): per
    mixer three passes (forward, and two of the size of the forward for
    the gradients), each the larger of the FLOPs (two gates and
    ``conv_L_cache`` taps a channel) / peak and the bytes of the (T, 3 d)
    input and the (T, d) output / bandwidth. ``(seconds, if
    compute-bound, if memory-bound)``."""
    tokens = sequences * seq_len
    d = model["hidden_size"]
    macs = tokens * d * (model["conv_L_cache"] + 2)
    one = lm_flops._least(3, macs, act_bytes * tokens * 4 * d, peak_flops,
                          peak_bytes_per_s)
    return tuple(layers(model)["conv"] * t for t in one)


def attention_least_seconds(model, sequences, seq_len, peak_flops,
                            peak_bytes_per_s, act_bytes=2):
    """The least time for the step's attention products (scores and
    values of the unmasked pairs at ``head_dim``): per attention layer
    three passes (forward, dQ, dK and dV), each the larger of FLOPs / peak
    and the bytes of q, k, v and o / bandwidth."""
    dim = model["head_dim"]
    hq, hkv = model["num_attention_heads"], model["num_key_value_heads"]
    macs = sequences * lm_flops.attended_pairs(seq_len) * hq * dim * 2
    bytes_ = act_bytes * sequences * seq_len * dim * (2 * hq + 2 * hkv)
    one = lm_flops._least(3, macs, bytes_, peak_flops, peak_bytes_per_s)
    return tuple(layers(model)["full_attention"] * t for t in one)
