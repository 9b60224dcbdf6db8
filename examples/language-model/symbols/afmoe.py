"""The ``afmoe`` decoder (arcee-ai Trinity family) as a symbol, built only
from registered ops: ``get_symbol(**config)`` with the keys of the model's
public ``config.json``.

Per layer (``RMS`` = ``_contrib_RMSNorm`` with a learned scale; no bias
anywhere):

- attention: ``a = RMS(h)``; q, k, v and an output gate g are projections
  of ``a``; q and k are normalised per head (QK-norm); on
  ``sliding_attention`` layers q and k are rotated (RoPE) and position i
  sees j with 0 <= i - j < ``sliding_window``, on ``full_attention``
  layers there is no rotation and i sees every j <= i; ``o = attention *
  sigmoid(g)``; ``h <- h + RMS(o Wo)``.
- feed-forward, the first ``num_dense_layers`` layers: ``m = RMS(h)``;
  ``h <- h + RMS((silu(m W1) * (m W3)) W2)`` of width
  ``intermediate_size``.
- experts, the other layers: ``m = RMS(h)``; ``h <- h + RMS(shared(m) +
  routed(m))``: one shared expert of width ``moe_intermediate_size`` and
  ``_contrib_MoE`` (sigmoid router over ``num_experts``, top
  ``num_experts_per_tok`` with a selection bias, normalised and scaled
  weights), which computes the terms of the ``experts_held`` experts.
- ends: ``h = E[ids] * sqrt(hidden_size)`` (``mup_enabled``); the loss is
  ``_contrib_TokenCrossEntropy`` of ``RMS(h) Wout`` against the labels,
  one float32 a position.

Every layer's first node starts a checkpoint segment
(``__mirror_stage__``): the backward pass holds one layer's activations
at a time.
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


def _attention(h, p, c, sliding):
    heads, kv_heads, dim = (c["num_attention_heads"],
                            c["num_key_value_heads"], c["head_dim"])
    eps = c["rms_norm_eps"]
    a = mx.sym._contrib_RMSNorm(h, name=p + "attn_norm", eps=eps,
                                attr={"__mirror_stage__": "1"})
    q = _linear(a, p + "attn_wq", heads * dim)
    k = _linear(a, p + "attn_wk", kv_heads * dim)
    v = _linear(a, p + "attn_wv", kv_heads * dim)
    g = _linear(a, p + "attn_wg", heads * dim)
    q = _norm(q, p + "attn_qnorm", eps, group_size=dim)
    k = _norm(k, p + "attn_knorm", eps, group_size=dim)
    if sliding:
        q = mx.sym._contrib_RotaryEmbedding(q, name=p + "attn_qrope",
                                            head_dim=dim,
                                            theta=c["rope_theta"])
        k = mx.sym._contrib_RotaryEmbedding(k, name=p + "attn_krope",
                                            head_dim=dim,
                                            theta=c["rope_theta"])
    o = mx.sym._contrib_CausalAttention(
        q, k, v, name=p + "attn_core", num_heads=heads,
        num_kv_heads=kv_heads,
        window=c["sliding_window"] if sliding else 0)
    o = o * mx.sym.Activation(g, name=p + "attn_gate", act_type="sigmoid")
    o = _norm(_linear(o, p + "attn_wo", c["hidden_size"]),
              p + "attn_postnorm", eps)
    return h + o


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


def get_symbol(dtype="bfloat16", **config):
    """The training symbol: data ``data`` (batch, T) ids, label ``label``
    (batch, T) next ids, output the loss of every position."""
    c = config
    d, eps = c["hidden_size"], c["rms_norm_eps"]
    ids = mx.sym.Variable("data")
    label = mx.sym.Variable("label")
    h = mx.sym.Embedding(ids, mx.sym.Variable("embed_weight", dtype=dtype),
                         name="embed", input_dim=c["vocab_size"],
                         output_dim=d)
    if c.get("mup_enabled"):
        h = h * math.sqrt(d)
    for i, kind in enumerate(c["layer_types"][:c["num_hidden_layers"]]):
        p = "l%d_" % i
        h = _attention(h, p, c, kind == "sliding_attention")
        m = _norm(h, p + "ffn_norm", eps)
        f = _gated(m, p + "ffn", c["intermediate_size"], d) \
            if i < c["num_dense_layers"] else _experts(m, p, c)
        h = h + _norm(f, p + "ffn_postnorm", eps)
    h = mx.sym._contrib_RMSNorm(h, name="final_norm", eps=eps,
                                attr={"__mirror_stage__": "1"})
    return mx.sym._contrib_TokenCrossEntropy(
        h, label=label, name="loss", num_classes=c["vocab_size"])
