"""Operations and bytes a hybrid state-space / sparse decoder's training
step requires (``nemotron_h``: Mamba-2 mixers, ungated routed experts with
a shared expert, grouped-query attention; one block a layer), counted from
its configuration.

As ``lm_flops.py``: the algorithm's count, one multiply-add is 2 FLOPs, a
training step is 3 x forward; attention counts the query-key pairs the
causal mask leaves; the routed experts count the rows routed to the
experts held here at a uniform load. The state-space recurrence is counted
in its chunked dual form with whole chunks (``C B^T`` and ``(L o C B^T)
(delta x)`` over ``chunk x chunk`` blocks, each chunk's state, each
position's read of the entering state; the pass of the states over the
chunks is a few multiply-adds a chunk and is left out), whatever
implements it. Elementwise work (the convolution's 4 taps, norms, gates,
decays) is not counted, here as there.
"""
from benchmarks.harness import lm_flops

KINDS = ("mamba", "moe", "attention")


def model_of(cfg, lm_model):
    """The sizes the counts need: the window's model (``lm["model"]``) over
    the symbol's keyword arguments of the configuration's file, which carry
    the Mamba sizes."""
    return dict(cfg["symbol"]["kwargs"], **lm_model)


def blocks(model):
    """How many blocks of each kind are kept."""
    kept = list(model["layer_types"])[:model.get("num_hidden_layers")]
    return {k: kept.count(k) for k in KINDS}


def ssd_macs_per_token(model):
    h, p = model["mamba_num_heads"], model["mamba_head_dim"]
    n, g, q = model["ssm_state_size"], model["n_groups"], model["chunk_size"]
    return g * q * n + h * q * p + 2 * h * p * n


def forward_macs(model, sequences, seq_len):
    """Multiply-adds of one forward pass over ``sequences`` sequences of
    ``seq_len`` tokens, by part."""
    d, dim = model["hidden_size"], model["head_dim"]
    hq, hkv = model["num_attention_heads"], model["num_key_value_heads"]
    inner = model["mamba_num_heads"] * model["mamba_head_dim"]
    bc = model["n_groups"] * model["ssm_state_size"]
    tokens = sequences * seq_len
    n = blocks(model)
    return {
        # in: d x (z, x, B, C, dt); out: inner x d
        "mamba_projections": n["mamba"] * tokens * d * (
            3 * inner + 2 * bc + model["mamba_num_heads"]),
        "ssd": n["mamba"] * tokens * ssd_macs_per_token(model),
        # q and output: d x heads*dim each; k, v: d x kv*dim
        "attention_projections": n["attention"] * tokens * d * dim
        * (2 * hq + 2 * hkv),
        "attention": n["attention"] * sequences
        * lm_flops.attended_pairs(seq_len) * hq * dim * 2,      # QK^T, PV
        "shared_expert": n["moe"] * tokens * 2 * d
        * model["moe_shared_expert_intermediate_size"]
        * model["num_shared_experts"],
        "router": n["moe"] * tokens * d * model["num_experts"],
        "routed_experts": n["moe"] * lm_flops.routed_rows(model, tokens)
        * 2 * d * model["moe_intermediate_size"],
        "head": tokens * d * model["vocab_size"],
    }


def train_step_flops(model, sequences, seq_len):
    """FLOPs one training step requires: 3 x forward, 2 per multiply-add."""
    return 3 * 2 * sum(forward_macs(model, sequences, seq_len).values())


def ssd_least_seconds(model, sequences, seq_len, peak_flops,
                      peak_bytes_per_s, act_bytes=2):
    """The least time the chip could take for the step's state-space
    recurrences: per mixer three passes (forward, and two of the size of
    the forward for the gradients), each the larger of the products' FLOPs
    / peak and the bytes of x, B, C, delta and y moved once / bandwidth.
    ``(seconds, if compute-bound, if memory-bound)``."""
    tokens = sequences * seq_len
    inner = model["mamba_num_heads"] * model["mamba_head_dim"]
    bytes_ = act_bytes * tokens * (
        2 * inner + 2 * model["n_groups"] * model["ssm_state_size"]
        + model["mamba_num_heads"])
    one = lm_flops._least(3, tokens * ssd_macs_per_token(model), bytes_,
                          peak_flops, peak_bytes_per_s)
    return tuple(blocks(model)["mamba"] * t for t in one)
