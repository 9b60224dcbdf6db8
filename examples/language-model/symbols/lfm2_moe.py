"""The ``lfm2_moe`` hybrid decoder (Liquid AI's LFM2-8B-A1B) as a symbol,
built only from registered ops: ``get_symbol(**config)``.

Every layer is ``h <- h + mixer(RMS(h))``, ``h <- h + ffn(RMS(h))`` with
pre-norms (``_contrib_RMSNorm``, a learned scale) and no bias in any
projection. The mixers, by ``layer_types`` (a list of ``"conv"`` /
``"full_attention"``):

- ``conv``: the gated short convolution. ``[B | C | x] = u W_in`` (d to 3
  d, ``*_conv_in``); ``y = C * conv(B * x)`` with a causal depthwise
  convolution of width ``conv_L_cache``, no bias and no activation
  (``_contrib_GatedShortConv``, ``*_shortconv``); out ``y W_out``
  (``*_conv_out``).
- ``full_attention``: grouped-query attention, ``num_attention_heads``
  query heads of ``head_dim`` on ``num_key_value_heads`` K/V heads; ``q``
  and ``k`` normed a head (one scale of ``head_dim`` each) and then
  rotated (``_contrib_RotaryEmbedding``, ``rope_theta``); causal softmax
  attention (``_contrib_CausalAttention``, ``*_attn_core``); out ``o
  W_o``.

The feed-forward of the first ``num_dense_layers`` layers is a SwiGLU of
``intermediate_size``; of the others ``_contrib_MoE`` alone (no shared
expert): a sigmoid router over ``num_experts``, top
``num_experts_per_tok`` of scores + a selection bias, the chosen scores
normalised and scaled by ``route_scale``, which computes the terms of the
``experts_held`` experts. Ends: ``h = E[ids]``; the loss is
``_contrib_TokenCrossEntropy`` of ``RMS(h) E^T``: the head is the
embedding's matrix, one leaf read by both ops, whose gradient is the sum
of the two uses.

The convolutions' taps are held as *offsets* (``*_shortconv_weight_offset``)
from a start spread by index uniform over ``+-1 / sqrt(conv_L_cache)``
(PyTorch's ``Conv1d`` start for one channel a group): an initialisation
that draws every leaf about nought then lands on a start at which the
convolution carries the mixer, and gradients and updates are those of the
parameters themselves.

Each layer is two checkpoint segments, its mixer and its feed-forward
(both pre-norms carry ``__mirror_stage__``): the backward pass holds half
a layer's activations at a time.
"""
import math

import mxnet_tpu as mx


def _linear(x, name, width):
    return mx.sym.FullyConnected(x, name=name, num_hidden=width,
                                 no_bias=True, flatten=False)


def _norm(x, name, eps, **kw):
    return mx.sym._contrib_RMSNorm(x, name=name, eps=eps, **kw)


def _gated(x, name, width, out_width):
    """``(silu(x W1) * (x W3)) W2``."""
    act = mx.sym._contrib_SiLUGate(_linear(x, name + "_w1", width),
                                   _linear(x, name + "_w3", width),
                                   name=name + "_act")
    return _linear(act, name + "_w2", out_width)


def _taps(name, channels, c):
    """The convolution's taps: a start uniform over ``+-1 / sqrt(L)``,
    entry i of the flattened weight ``((487 i mod 1021) + 0.5) / 1021`` of
    the way, plus the learned offset."""
    k = c["conv_L_cache"]
    bound = 1.0 / math.sqrt(k)
    at = mx.sym._mod_scalar(mx.sym._mul_scalar(
        mx.sym.arange(0, channels * k, name=name + "_tap_at"),
        name=name + "_tap_mul", scalar=487.0), name=name + "_tap_mod",
        scalar=1021.0)
    start = mx.sym.reshape(mx.sym._plus_scalar(mx.sym._mul_scalar(
        at, name=name + "_start_mul", scalar=2 * bound / 1021.0),
        name=name + "_start_flat", scalar=bound / 1021.0 - bound),
        name=name + "_start", shape=(channels, k))
    return start + mx.sym.Variable(name + "_weight_offset",
                                   shape=(channels, k), dtype=c["dtype"])


def _conv(u, p, c):
    d = c["hidden_size"]
    bcx = _linear(u, p + "conv_in", 3 * d)
    y = mx.sym._contrib_GatedShortConv(bcx, _taps(p + "shortconv", d, c),
                                       name=p + "shortconv",
                                       kernel=c["conv_L_cache"])
    return _linear(y, p + "conv_out", d)


def _attention(u, p, c):
    heads, kv_heads, dim = (c["num_attention_heads"],
                            c["num_key_value_heads"], c["head_dim"])
    eps = c["rms_norm_eps"]
    p = p + "attn_"
    q = _norm(_linear(u, p + "wq", heads * dim), p + "qnorm", eps,
              group_size=dim)
    k = _norm(_linear(u, p + "wk", kv_heads * dim), p + "knorm", eps,
              group_size=dim)
    q, k = (mx.sym._contrib_RotaryEmbedding(x, name=p + s, head_dim=dim,
                                            theta=c["rope_theta"])
            for x, s in ((q, "qrope"), (k, "krope")))
    o = mx.sym._contrib_CausalAttention(
        q, k, _linear(u, p + "wv", kv_heads * dim), name=p + "core",
        num_heads=heads, num_kv_heads=kv_heads, window=0)
    return _linear(o, p + "wo", c["hidden_size"])


def _experts(m, p, c):
    held = c.get("experts_held") or (0, c["num_experts"])
    return mx.sym._contrib_MoE(
        m, name=p + "moe", num_experts=c["num_experts"],
        top_k=c["num_experts_per_tok"], hidden=c["moe_intermediate_size"],
        experts_held=tuple(held), score_func=c["score_func"],
        route_norm=c["route_norm"], route_scale=c["route_scale"],
        load_balance_coeff=c["load_balance_coeff"])


_MIXERS = {"conv": _conv, "full_attention": _attention}


def get_symbol(dtype="bfloat16", **config):
    """The training symbol: data ``data`` (batch, T) ids, label ``label``
    (batch, T) next ids, output the loss of every position."""
    c = dict(config, dtype=dtype)
    d, eps = c["hidden_size"], c["rms_norm_eps"]
    embed = mx.sym.Variable("embed_weight", dtype=dtype)
    h = mx.sym.Embedding(mx.sym.Variable("data"), embed, name="embed",
                         input_dim=c["vocab_size"], output_dim=d)
    for i, kind in enumerate(c["layer_types"][:c["num_hidden_layers"]]):
        p = "l%d_" % i
        u = _norm(h, p + "attn_norm", eps, attr={"__mirror_stage__": "1"})
        h = h + _MIXERS[kind](u, p, c)
        m = _norm(h, p + "ffn_norm", eps, attr={"__mirror_stage__": "1"})
        h = h + (_gated(m, p + "ffn", c["intermediate_size"], d)
                 if i < c["num_dense_layers"] else _experts(m, p, c))
    h = _norm(h, "final_norm", eps, attr={"__mirror_stage__": "1"})
    return mx.sym._contrib_TokenCrossEntropy(
        h, embed, label=mx.sym.Variable("label"), name="loss",
        num_classes=c["vocab_size"])
