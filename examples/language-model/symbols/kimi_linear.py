"""The ``kimi_linear`` hybrid decoder (Moonshot's Kimi Linear, arXiv:2510.26692)
as a symbol, built only from registered ops: ``get_symbol(**config)``.

Every layer is ``h <- h + mixer(RMS(h))``, ``h <- h + ffn(RMS(h))`` with
pre-norms (``_contrib_RMSNorm``, a learned scale) and no bias in any
projection. The mixers, by ``layer_types`` (a list of ``"kda"`` / ``"mla"``):

- ``kda``: Kimi Delta Attention, ``kda_num_heads`` heads of
  ``kda_head_dim`` (keys and values alike). ``q, k, v = silu(conv(u W))``
  with three projections and three causal depthwise convolutions of width
  ``short_conv_kernel_size`` without a bias (``_contrib_CausalConv1D``);
  the decay's argument ``(u W_f1) W_f2`` through a waist of
  ``kda_head_dim``, and ``beta``'s ``u w_b`` a head; the recurrence is
  ``_contrib_KDA`` (an L2 norm a head on q and k, ``g = -exp(A_log)
  softplus(. + dt_bias)`` a channel, ``S_t = (I - b_t k_t k_t^T)
  Diag(e^(g_t)) S_{t-1} + b_t k_t v_t^T``, ``o_t = S_t^T q_t``); then
  ``RMS_head(o) * sigmoid((u W_g1) W_g2)`` with one scale of
  ``kda_head_dim`` shared by the heads (``_contrib_GatedRMSNorm``, the norm
  first); out ``o W_o``.
- ``mla``: latent attention without positions. ``q = u W_q``
  (``num_attention_heads`` heads of ``qk_nope_head_dim +
  qk_rope_head_dim``); ``[c | k_pe] = u W_kva`` (``kv_lora_rank`` +
  ``qk_rope_head_dim``); ``c <- RMS(c)``; ``[k_nope | v] = c W_kvb`` a head
  (``qk_nope_head_dim + v_head_dim``); a head's key is ``[k_nope | k_pe]``,
  the ``k_pe`` columns the same for every head; no rotation on either part
  (``mla_use_nope``); causal softmax attention with keys of 192 and values
  of 128 (``_contrib_CausalAttention``, scale ``1 / sqrt(192)``); out ``o
  W_o``. The latent is expanded: no absorbed form, no cache.

The feed-forward of the first ``num_dense_layers`` layers is a SwiGLU of
``intermediate_size``; of the others ``shared(m) + routed(m)``: a shared
SwiGLU expert and ``_contrib_MoE`` (sigmoid router over ``num_experts``, top
``num_experts_per_tok`` of scores + a selection bias, the chosen scores
normalised and scaled by ``route_scale``), which computes the terms of the
``experts_held`` experts. Ends: ``h = E[ids]``; the loss is
``_contrib_TokenCrossEntropy`` of ``RMS(h) Wout`` against the labels.

``A_log``, ``dt_bias`` and the convolutions' taps are held as *offsets*
(``*_A_log_offset``, ``*_dt_bias_offset``, ``*_weight_offset``) from
starting values spread by index: ``A`` evenly over 1..16 by head,
``dt_bias`` the inverse softplus of steps log-spaced over 0.001..0.1 by
channel (every head spans the range: channel c of a head
stands ``((37 c mod head_dim) + 0.5) / head_dim`` of the way), the taps
uniform over ``+-1 / sqrt(kernel)`` by (channel, tap). An initialisation
that draws every leaf about nought then lands on a start at which the
recurrence does something, and gradients and updates are those of the
parameters themselves.

Each layer is two checkpoint segments, its mixer and its feed-forward
(both pre-norms carry ``__mirror_stage__``): the backward pass holds half
a layer's activations at a time. One segment a layer read 0.16 GB more in
the offline buffer assignment of the benchmark's cell, which has no such
room.
"""
import math

import mxnet_tpu as mx


#: where ``A`` (by head) and the step ``softplus(dt_bias)`` (by channel)
#: start
_A_START = (1.0, 16.0)
_DT_START = (0.001, 0.1)


def _linear(x, name, width):
    return mx.sym.FullyConnected(x, name=name, num_hidden=width,
                                 no_bias=True, flatten=False)


def _norm(x, name, eps, **kw):
    return mx.sym._contrib_RMSNorm(x, name=name, eps=eps, **kw)


def _part(x, name, begin, end, axis=-1):
    return mx.sym.slice_axis(x, name=name, axis=axis, begin=begin, end=end)


def _gated(x, name, width, out_width):
    """``(silu(x W1) * (x W3)) W2``."""
    act = mx.sym._contrib_SiLUGate(_linear(x, name + "_w1", width),
                                   _linear(x, name + "_w3", width),
                                   name=name + "_act")
    return _linear(act, name + "_w2", out_width)


def _affine(x, name, mul, add):
    return mx.sym._plus_scalar(
        mx.sym._mul_scalar(x, name=name + "_mul", scalar=mul),
        name=name, scalar=add)


def _spread(p, count, mul, mod):
    """``((mul i mod mod) + 0.5) / mod`` for i < count, float32: whole
    numbers under 2^24, so every step is exact."""
    at = mx.sym._mod_scalar(mx.sym._mul_scalar(
        mx.sym.arange(0, count, name=p + "at"), name=p + "at_mul",
        scalar=float(mul)), name=p + "at_mod", scalar=float(mod))
    return _affine(at, p + "at_share", 1.0 / mod, 0.5 / mod)


def _kda_start(p, c):
    """``(A_log (heads,), dt_bias (heads * dim,))`` float32: the starting
    values by index plus the learned offsets."""
    h, dim = c["kda_num_heads"], c["kda_head_dim"]
    lo, hi = _A_START
    t_lo, t_hi = _DT_START
    at = mx.sym._div_scalar(mx.sym.arange(0, h, name=p + "head"),
                            name=p + "head_at", scalar=float(max(h - 1, 1)))
    a_log = mx.sym.log(_affine(at, p + "A_start", hi - lo, lo),
                       name=p + "A_log_start")
    step = mx.sym.exp(_affine(_spread(p + "dt_", h * dim, 37, dim),
                              p + "dt_log_start",
                              math.log(t_hi) - math.log(t_lo),
                              math.log(t_lo)), name=p + "dt_start")
    # softplus^-1(step) = step + log(1 - exp(-step))
    inv = step + mx.sym.log(mx.sym.negative(mx.sym.expm1(
        mx.sym.negative(step, name=p + "dt_neg"), name=p + "dt_expm1"),
        name=p + "dt_one_minus"), name=p + "dt_log1m")

    def offset(name, n):
        return mx.sym.Variable(p + name, shape=(n,), dtype="float32")

    return (a_log + offset("A_log_offset", h),
            inv + offset("dt_bias_offset", h * dim))


def _short_conv(x, name, channels, c):
    """``silu(conv(x))``: causal, depthwise, no bias; the taps start over
    ``+-1 / sqrt(kernel)``, entry i of the flattened weight ``((487 i mod
    1021) + 0.5) / 1021`` of the way, plus the learned offset."""
    k = c["short_conv_kernel_size"]
    bound = 1.0 / math.sqrt(k)
    start = mx.sym.reshape(
        _affine(_spread(name + "_tap_", channels * k, 487, 1021),
                name + "_start_flat", 2 * bound, -bound),
        name=name + "_start", shape=(channels, k))
    taps = start + mx.sym.Variable(name + "_weight_offset",
                                   shape=(channels, k), dtype=c["dtype"])
    return mx.sym._contrib_CausalConv1D(x, taps, name=name, kernel=k,
                                        no_bias=True)


def _kda(u, p, c):
    h, dim = c["kda_num_heads"], c["kda_head_dim"]
    inner = h * dim
    p = p + "kda_"
    q, k, v = (_short_conv(_linear(u, p + "w" + s, inner), p + "conv_" + s,
                           inner, c) for s in "qkv")
    gate = _linear(_linear(u, p + "f1", dim), p + "f2", inner)
    a_log, dt_bias = _kda_start(p, c)
    o = mx.sym._contrib_KDA(q, k, v, gate, _linear(u, p + "wb", h), a_log,
                            dt_bias, name=p + "core", heads=h,
                            chunk=c.get("kda_chunk", 64),
                            sub=c.get("kda_sub", 16))
    o = mx.sym._contrib_GatedRMSNorm(
        o, _linear(_linear(u, p + "g1", dim), p + "g2", inner),
        name=p + "norm", eps=c["rms_norm_eps"], group_size=dim,
        gate_act="sigmoid", norm_first=True)
    return _linear(o, p + "wo", c["hidden_size"])


def _mla(u, p, c):
    heads, rank = c["num_attention_heads"], c["kv_lora_rank"]
    nope, rope, dv = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                      c["v_head_dim"])
    p = p + "attn_"
    q = _linear(u, p + "wq", heads * (nope + rope))
    kva = _linear(u, p + "wkva", rank + rope)
    latent = _norm(_part(kva, p + "latent", 0, rank), p + "kva_norm",
                   c["rms_norm_eps"])
    kvb = mx.sym.reshape(_linear(latent, p + "wkvb", heads * (nope + dv)),
                         name=p + "kvb_heads", shape=(0, 0, heads, nope + dv))
    # the position part of a key is one for all heads
    k_pe = mx.sym.broadcast_axis(
        mx.sym.reshape(_part(kva, p + "k_pe", rank, rank + rope),
                       name=p + "k_pe_head", shape=(0, 0, 1, rope)),
        name=p + "k_pe_heads", axis=2, size=heads)
    k = mx.sym.reshape(
        mx.sym.Concat(_part(kvb, p + "k_nope", 0, nope), k_pe, dim=3,
                      name=p + "k_heads"),
        name=p + "k", shape=(0, 0, -1))
    v = mx.sym.reshape(_part(kvb, p + "v_heads", nope, nope + dv),
                       name=p + "v", shape=(0, 0, -1))
    o = mx.sym._contrib_CausalAttention(q, k, v, name=p + "core",
                                        num_heads=heads, num_kv_heads=heads,
                                        window=0)
    return _linear(o, p + "wo", c["hidden_size"])


def _experts(m, p, c):
    d = c["hidden_size"]
    shared = _gated(m, p + "shared", c["moe_intermediate_size"]
                    * c["num_shared_experts"], d)
    held = c.get("experts_held") or (0, c["num_experts"])
    routed = mx.sym._contrib_MoE(
        m, name=p + "moe", num_experts=c["num_experts"],
        top_k=c["num_experts_per_tok"], hidden=c["moe_intermediate_size"],
        experts_held=tuple(held), score_func=c["score_func"],
        route_norm=c["route_norm"], route_scale=c["route_scale"],
        load_balance_coeff=c["load_balance_coeff"])
    return shared + routed


_MIXERS = {"kda": _kda, "mla": _mla}


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
    for i, kind in enumerate(c["layer_types"][:c["num_hidden_layers"]]):
        p = "l%d_" % i
        u = _norm(h, p + "attn_norm", eps, attr={"__mirror_stage__": "1"})
        h = h + _MIXERS[kind](u, p, c)
        m = _norm(h, p + "ffn_norm", eps, attr={"__mirror_stage__": "1"})
        h = h + (_gated(m, p + "ffn", c["intermediate_size"], d)
                 if i < c["num_dense_layers"] else _experts(m, p, c))
    h = _norm(h, "final_norm", eps, attr={"__mirror_stage__": "1"})
    return mx.sym._contrib_TokenCrossEntropy(
        h, label=label, name="loss", num_classes=c["vocab_size"])
