"""Operations and bytes a ``kimi_linear`` decoder's training step requires
(Kimi Delta Attention layers, latent attention with keys wider than
values, a leading dense SwiGLU, sigmoid-routed SwiGLU experts with a shared
expert), counted from its configuration.

As ``lm_flops.py``: the algorithm's count, one multiply-add is 2 FLOPs, a
training step is 3 x forward; attention counts the query-key pairs the
causal mask leaves, the scores at the keys' width and the values at
theirs; the routed experts count the rows routed to the experts held here
at a uniform load. The delta-rule recurrence is counted in its chunked
form with whole chunks, whatever implements it: the chunk's two blocks
``K K^T`` and ``Q K^T`` with their decays (chunk x key width a position
each), the inverse of the unit lower block by doubling (``2 (log2 chunk -
1)`` products of chunk^3), ``W = T (K e^G)`` and ``U = T V``, and a
position's share of the four products with the state (``W S``, ``K'^T U~``,
``Q S`` at key x value width, ``Aqk U~`` at chunk x value width).
Elementwise work (the convolutions' taps, norms, gates, decays, the blocks'
exponentials) is not counted, here as there.
"""
import math

from benchmarks.harness import lm_flops
from benchmarks.harness.hybrid_flops import model_of     # noqa: F401 -- the
# window's model over the symbol's keyword arguments, which carry the KDA
# and MLA sizes

KINDS = ("kda", "mla")


def layers(model):
    """How many layers of each mixer are kept."""
    kept = list(model["layer_types"])[:model["num_hidden_layers"]]
    return {k: kept.count(k) for k in KINDS}


def kda_macs_per_token(model):
    """The recurrence's multiply-adds a position, all heads."""
    h, d, q = (model["kda_num_heads"], model["kda_head_dim"],
               model.get("kda_chunk", 64))
    blocks = 2 * q * d                      # Akk, Aqk
    inverse = 2 * (int(math.log2(q)) - 1) * q * q
    wu = 2 * q * d                          # T (K e^G), T V
    state = 3 * d * d + q * d               # W S, K'^T U~, Q S; Aqk U~
    return h * (blocks + inverse + wu + state)


def mla_pair_macs(model):
    """Multiply-adds of one attended query-key pair, all heads."""
    return model["num_attention_heads"] * (
        model["qk_nope_head_dim"] + model["qk_rope_head_dim"]
        + model["v_head_dim"])


def forward_macs(model, sequences, seq_len):
    """Multiply-adds of one forward pass over ``sequences`` sequences of
    ``seq_len`` tokens, by part."""
    d, f = model["hidden_size"], model["moe_intermediate_size"]
    h, dim = model["kda_num_heads"], model["kda_head_dim"]
    heads, rank = model["num_attention_heads"], model["kv_lora_rank"]
    nope, rope, dv = (model["qk_nope_head_dim"], model["qk_rope_head_dim"],
                      model["v_head_dim"])
    tokens = sequences * seq_len
    n = layers(model)
    kept = n["kda"] + n["mla"]
    dense = min(model["num_dense_layers"], kept)
    return {
        # q, k, v, out: d x h*dim each; the decay's and the gate's waists;
        # beta
        "kda_projections": n["kda"] * tokens * (
            4 * d * h * dim + 2 * (d * dim + dim * h * dim) + d * h),
        "kda": n["kda"] * tokens * kda_macs_per_token(model),
        "mla_projections": n["mla"] * tokens * (
            d * heads * (nope + rope) + d * (rank + rope)
            + rank * heads * (nope + dv) + heads * dv * d),
        "mla_attention": n["mla"] * sequences
        * lm_flops.attended_pairs(seq_len) * mla_pair_macs(model),
        "dense_ffn": dense * tokens * 3 * d * model["intermediate_size"],
        "shared_expert": (kept - dense) * tokens * 3 * d * f
        * model["num_shared_experts"],
        "router": (kept - dense) * tokens * d * model["num_experts"],
        "routed_experts": (kept - dense)
        * lm_flops.routed_rows(model, tokens) * 3 * d * f,
        "head": tokens * d * model["vocab_size"],
    }


def train_step_flops(model, sequences, seq_len):
    """FLOPs one training step requires: 3 x forward, 2 per multiply-add."""
    return 3 * 2 * sum(forward_macs(model, sequences, seq_len).values())


def kda_least_seconds(model, sequences, seq_len, peak_flops,
                      peak_bytes_per_s, act_bytes=2):
    """The least time the chip could take for the step's delta-rule
    recurrences: per KDA layer three passes (forward, and two of the size of
    the forward for the gradients), each the larger of the chunked form's
    FLOPs / peak and the bytes of q, k, v, g, beta and o moved once /
    bandwidth. ``(seconds, if compute-bound, if memory-bound)``."""
    tokens = sequences * seq_len
    h, dim = model["kda_num_heads"], model["kda_head_dim"]
    bytes_ = act_bytes * tokens * (5 * h * dim + h)
    one = lm_flops._least(3, tokens * kda_macs_per_token(model), bytes_,
                          peak_flops, peak_bytes_per_s)
    return tuple(layers(model)["kda"] * t for t in one)


def mla_attention_least_seconds(model, sequences, seq_len, peak_flops,
                                peak_bytes_per_s, act_bytes=2):
    """The least time for the step's latent-attention products (the
    unmasked pairs' scores at the keys' width and values at theirs): per
    MLA layer three passes (forward, dQ, dK and dV), each the larger of
    FLOPs / peak and the bytes of q, k, v and o / bandwidth."""
    heads = model["num_attention_heads"]
    dqk = model["qk_nope_head_dim"] + model["qk_rope_head_dim"]
    macs = sequences * lm_flops.attended_pairs(seq_len) \
        * mla_pair_macs(model)
    bytes_ = act_bytes * sequences * seq_len * heads \
        * 2 * (dqk + model["v_head_dim"])
    one = lm_flops._least(3, macs, bytes_, peak_flops, peak_bytes_per_s)
    return tuple(layers(model)["mla"] * t for t in one)
