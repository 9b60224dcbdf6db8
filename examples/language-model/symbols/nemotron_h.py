"""The ``nemotron_h`` hybrid decoder (NVIDIA Nemotron-H / Nemotron 3 family)
as a symbol, built only from registered ops: ``get_symbol(**config)``.

One block a layer, each ``h <- h + mixer(RMS(h))`` with one pre-norm
(``_contrib_RMSNorm``, a learned scale), no bias in any projection. The
kinds, by ``layer_types`` (a list of ``"mamba"`` / ``"moe"`` /
``"attention"``, or the config's ``hybrid_override_pattern`` string of
``M`` / ``E`` / ``*``):

- ``mamba``: a Mamba-2 mixer. ``[z | xBC | dt] = u W_in`` of widths
  ``heads * head_dim`` | ``heads * head_dim + 2 * groups * state`` |
  ``heads``; ``xBC <- silu(conv(xBC) + b)``, a causal depthwise convolution
  of width ``conv_kernel`` (``_contrib_CausalConv1D``); ``x, B, C`` are its
  three parts; ``y = SSD(x, dt, B, C)`` (``_contrib_SSD``: ``delta =
  softplus(dt + dt_bias)``, ``S_t = exp(-exp(A_log) delta_t) S_{t-1} +
  delta_t x_t B_t^T``, ``y_t = S_t C_t + D x_t``); ``y <- RMS_group(y *
  silu(z))`` over groups of ``heads * head_dim / groups`` features with a
  scale a feature (``_contrib_GatedRMSNorm``); out ``y W_out``.
- ``moe``: ``shared(m) + routed(m)``: one shared expert ``relu(m W1)^2
  W2`` of width ``moe_shared_expert_intermediate_size`` and
  ``_contrib_MoEUngated`` (sigmoid router over ``num_experts``, top
  ``num_experts_per_tok`` of scores + a selection bias, the chosen scores
  normalised and scaled by ``route_scale``; experts ``relu(m W1)^2 W2`` of
  width ``moe_intermediate_size``), which computes the terms of the
  ``experts_held`` experts.
- ``attention``: ``num_attention_heads`` query heads on
  ``num_key_value_heads`` K/V heads of ``head_dim``, full causal, no
  rotation and no other position signal, no q/k norm, no gate.
- ends: ``h = E[ids]``; the loss is ``_contrib_TokenCrossEntropy`` of
  ``RMS(h) Wout`` against the labels, one float32 a position.

``A_log`` and ``dt_bias`` are held as *offsets* (``*_A_log_offset``,
``*_dt_bias_offset``, float32) from Mamba-2's starting values, spread by
head index over their ranges: ``A`` over ``a_init_range``, ``delta``'s
inverse softplus over ``time_step_min .. time_step_max`` (log-spaced). An
initialisation that draws every leaf about nought then lands on the
published one, and gradients and updates are those of the published
parameters. ``D`` is ``*_D_gamma`` (its start is 1). The convolution's
weight is held the same way (``*_conv_weight_offset``) from PyTorch's
``Conv1d`` start, uniform over ``+-1 / sqrt(conv_kernel)``, spread by
(channel, tap) index: with taps drawn about nought the recurrence's part
of a mixer's output is a thousandth of ``D x``'s and no comparison with a
reference could tell whether it was computed.

Every block's first node starts a checkpoint segment
(``__mirror_stage__``): the backward pass holds one block's activations
at a time.
"""
import math

import mxnet_tpu as mx

KINDS = {"M": "mamba", "E": "moe", "*": "attention"}


def _linear(x, name, width):
    return mx.sym.FullyConnected(x, name=name, num_hidden=width,
                                 no_bias=True, flatten=False)


def _part(x, name, begin, end):
    return mx.sym.slice_axis(x, name=name, axis=-1, begin=begin, end=end)


def _relu2_ffn(x, name, width, out_width):
    """``relu(x W1)^2 W2``."""
    a = mx.sym.Activation(_linear(x, name + "_w1", width),
                          name=name + "_relu", act_type="relu")
    return _linear(mx.sym.square(a, name=name + "_act"), name + "_w2",
                   out_width)


def _affine(x, name, mul, add):
    return mx.sym._plus_scalar(
        mx.sym._mul_scalar(x, name=name + "_mul", scalar=mul),
        name=name, scalar=add)


def _mamba_start(p, c):
    """``(A_log, dt_bias)`` (heads,) float32: the starting values by head
    index plus the learned offsets."""
    h = c["mamba_num_heads"]
    lo, hi = c.get("a_init_range", (1.0, 16.0))
    t_lo, t_hi = c["time_step_min"], c["time_step_max"]
    at = mx.sym._div_scalar(mx.sym.arange(0, h, name=p + "head"),
                            name=p + "head_at", scalar=float(max(h - 1, 1)))
    a_log = mx.sym.log(_affine(at, p + "A_start", hi - lo, lo),
                       name=p + "A_log_start")
    delta = mx.sym.exp(_affine(at, p + "dt_log_start",
                               math.log(t_hi) - math.log(t_lo),
                               math.log(t_lo)), name=p + "dt_start")
    # softplus^-1(delta) = delta + log(1 - exp(-delta))
    inv = delta + mx.sym.log(mx.sym.negative(mx.sym.expm1(
        mx.sym.negative(delta, name=p + "dt_neg"), name=p + "dt_expm1"),
        name=p + "dt_one_minus"), name=p + "dt_log1m")

    def offset(name):
        return mx.sym.Variable(p + name, shape=(h,), dtype="float32")

    return a_log + offset("A_log_offset"), inv + offset("dt_bias_offset")


def _conv_start(p, channels, k):
    """(channels, k) float32 over ``+-1 / sqrt(k)``: entry i of the
    flattened weight is ``((487 i mod 1021) + 0.5) / 1021`` of the way (whole
    numbers under 2^24, so float32 holds every step exactly)."""
    at = mx.sym._mod_scalar(mx.sym._mul_scalar(
        mx.sym.arange(0, channels * k, name=p + "conv_tap"),
        name=p + "conv_tap_mul", scalar=487.0),
        name=p + "conv_tap_mod", scalar=1021.0)
    bound = 1.0 / math.sqrt(k)
    return mx.sym.reshape(
        _affine(at, p + "conv_start_flat", 2 * bound / 1021.0,
                bound / 1021.0 - bound),
        name=p + "conv_start", shape=(channels, k))


def _mamba(u, p, c):
    h, dim = c["mamba_num_heads"], c["mamba_head_dim"]
    n, g = c["ssm_state_size"], c["n_groups"]
    inner, bc = h * dim, g * n
    p = p + "mixer_"
    proj = _linear(u, p + "in", 2 * inner + 2 * bc + h)
    z = _part(proj, p + "z", 0, inner)
    xbc = _part(proj, p + "xbc", inner, 2 * inner + 2 * bc)
    dt = _part(proj, p + "dt", 2 * inner + 2 * bc, 2 * inner + 2 * bc + h)
    k = c["conv_kernel"]
    taps = _conv_start(p, inner + 2 * bc, k) + mx.sym.Variable(
        p + "conv_weight_offset", shape=(inner + 2 * bc, k), dtype=c["dtype"])
    xbc = mx.sym._contrib_CausalConv1D(xbc, taps, name=p + "conv", kernel=k)
    a_log, dt_bias = _mamba_start(p, c)
    y = mx.sym._contrib_SSD(
        _part(xbc, p + "x", 0, inner), dt,
        _part(xbc, p + "B", inner, inner + bc),
        _part(xbc, p + "C", inner + bc, inner + 2 * bc), a_log, dt_bias,
        mx.sym.Variable(p + "D_gamma", shape=(h,), dtype="float32"),
        name=p + "ssd", heads=h, head_dim=dim, state=n, groups=g,
        chunk=c["chunk_size"])
    y = mx.sym._contrib_GatedRMSNorm(y, z, name=p + "norm",
                                     eps=c["rms_norm_eps"],
                                     group_size=inner // g)
    return _linear(y, p + "out", c["hidden_size"])


def _experts(m, p, c):
    d = c["hidden_size"]
    shared = _relu2_ffn(m, p + "shared",
                        c["moe_shared_expert_intermediate_size"]
                        * c["num_shared_experts"], d)
    held = c.get("experts_held") or (0, c["num_experts"])
    routed = mx.sym._contrib_MoEUngated(
        m, name=p + "moe", num_experts=c["num_experts"],
        top_k=c["num_experts_per_tok"], hidden=c["moe_intermediate_size"],
        experts_held=tuple(held), score_func=c["score_func"],
        route_norm=c["route_norm"], route_scale=c["route_scale"],
        load_balance_coeff=c["load_balance_coeff"],
        act=c.get("mlp_hidden_act", "relu2"))
    return shared + routed


def _attention(a, p, c):
    heads, kv_heads, dim = (c["num_attention_heads"],
                            c["num_key_value_heads"], c["head_dim"])
    o = mx.sym._contrib_CausalAttention(
        _linear(a, p + "attn_wq", heads * dim),
        _linear(a, p + "attn_wk", kv_heads * dim),
        _linear(a, p + "attn_wv", kv_heads * dim), name=p + "attn_core",
        num_heads=heads, num_kv_heads=kv_heads, window=0)
    return _linear(o, p + "attn_wo", c["hidden_size"])


_BLOCKS = {"mamba": _mamba, "moe": _experts, "attention": _attention}


def block_kinds(layer_types, num_hidden_layers=None):
    """The kinds of the blocks kept, from a list of kinds or a pattern
    string of ``M`` / ``E`` / ``*``."""
    kinds = [KINDS[k] for k in layer_types] \
        if isinstance(layer_types, str) else list(layer_types)
    return kinds[:num_hidden_layers]


def get_symbol(dtype="bfloat16", **config):
    """The training symbol: data ``data`` (batch, T) ids, label ``label``
    (batch, T) next ids, output the loss of every position."""
    c = dict(config, dtype=dtype)
    d, eps = c["hidden_size"], c["rms_norm_eps"]
    ids = mx.sym.Variable("data")
    label = mx.sym.Variable("label")
    h = mx.sym.Embedding(ids, mx.sym.Variable("embed_weight", dtype=dtype),
                         name="embed", input_dim=c["vocab_size"],
                         output_dim=d)
    for i, kind in enumerate(block_kinds(c["layer_types"],
                                         c.get("num_hidden_layers"))):
        p = "l%d_" % i
        u = mx.sym._contrib_RMSNorm(h, name=p + "norm", eps=eps,
                                    attr={"__mirror_stage__": "1"})
        h = h + _BLOCKS[kind](u, p, c)
    h = mx.sym._contrib_RMSNorm(h, name="final_norm", eps=eps,
                                attr={"__mirror_stage__": "1"})
    return mx.sym._contrib_TokenCrossEntropy(
        h, label=label, name="loss", num_classes=c["vocab_size"])
