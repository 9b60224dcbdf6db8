"""Operators of today's decoder blocks (new-framework extension: the 2017
reference predates all of them): RMSNorm, rotary embedding, the SiLU
gate, causal grouped-query attention with an optional window, the
mixture-of-experts layer with its selection bias as an auxiliary state,
and a token-level cross-entropy head that holds a small output.

Layout: activations are ``(batch, T, features)``; heads lie side by side
in the feature axis (``heads * head_dim``), as ``FullyConnected`` with
``flatten=False`` produces them.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .common import as_tuple
from .registry import register, get_op

_F32 = jnp.float32


@register("_contrib_RMSNorm", nin=2, arg_names=["data", "gamma"],
          defaults={"eps": 1e-5, "group_size": 0})
def rms_norm(data, gamma, eps=1e-5, group_size=0):
    """``data / sqrt(mean(data^2) + eps) * gamma`` over the last axis, or
    with ``group_size`` over each run of that many features (one head of
    a ``heads * head_dim`` axis; ``gamma`` is then ``(group_size,)``).
    Statistics in float32, the result in ``data``'s type."""
    shape = data.shape
    g = int(group_size)
    x = data.astype(_F32)
    if g:
        x = x.reshape(shape[:-1] + (shape[-1] // g, g))
    r = jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (x * r * gamma.astype(_F32)).reshape(shape).astype(data.dtype)


def _rms_shapes(shapes, params):
    return {1: (int(params.get("group_size", 0)) or shapes[0][-1],)}


def _f32_inputs(*idxs):
    def infer(in_types, params):
        return {i: np.float32 for i in idxs}
    return infer


@register("_contrib_RotaryEmbedding", defaults={"head_dim": 0,
                                                "theta": 10000.0})
def rotary_embedding(data, head_dim=0, theta=10000.0):
    """Rotate each head of ``data`` (batch, T, heads * head_dim) by its
    position: the pairs are (x[i], x[i + head_dim/2]) at frequency
    ``theta ** (-2i / head_dim)`` (the half-split form), position = index
    along T. Angles and the rotation in float32."""
    d = int(head_dim)
    b, t, f = data.shape
    half = d // 2
    inv = jnp.asarray(theta, _F32) ** (-jnp.arange(half, dtype=_F32)
                                       * 2.0 / d)
    ang = jnp.arange(t, dtype=_F32)[:, None] * inv[None]     # (T, half)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x = data.astype(_F32).reshape(b, t, f // d, d)
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return out.reshape(data.shape).astype(data.dtype)


@register("_contrib_SiLUGate", nin=2, arg_names=["gate", "up"])
def silu_gate(gate, up):
    """``silu(gate) * up``: the middle of a gated feed-forward."""
    return (jax.nn.silu(gate.astype(_F32)) * up.astype(_F32)
            ).astype(gate.dtype)


@register("_contrib_CausalAttention", nin=3,
          arg_names=["query", "key", "value"],
          defaults={"num_heads": 1, "num_kv_heads": 0, "window": 0})
def causal_attention(query, key, value, num_heads=1, num_kv_heads=0,
                     window=0):
    """Causal attention over (batch, T, heads * head_dim) operands.
    ``num_kv_heads`` (default ``num_heads``) K/V heads are shared by
    ``num_heads / num_kv_heads`` query heads each; position i sees j <= i
    and, with ``window``, only i - j < window (itself counted); scores
    ``q.k / sqrt(head_dim)``, softmax in float32. Forward and backward are
    the Pallas kernels of ``pallas.flash_attention`` (interpreted off the
    TPU).

    In a mirrored graph (``__mirror_stage__`` segments, or
    ``MXNET_BACKWARD_DO_MIRROR``) every node of this op holds its output
    in head-major form and the rows' log-sum-exp between forward and
    backward (batch x T x heads x head_dim in the operands' type, 2 bytes
    each in bfloat16, and 4 bytes x batch x T x heads), so that the backward pass does not run the forward
    kernel a second time: memory traded for the kernel's time, on
    purpose. Queries, keys and values are made again from the segment's
    input."""
    from ..pallas.flash_attention import flash_attention
    hq, hkv = int(num_heads), int(num_kv_heads) or int(num_heads)
    b, t, f = query.shape
    d = f // hq

    def heads(x, h):
        return x.reshape(b, t, h, d).transpose(0, 2, 1, 3)

    o = flash_attention(heads(query, hq), heads(key, hkv), heads(value, hkv),
                        True, None, None, None, int(window) or None)
    return o.transpose(0, 2, 1, 3).reshape(b, t, f)


@register("_contrib_MoE", nin=7, nout=2,
          arg_names=["data", "router_weight", "expert_w1_weight",
                     "expert_w3_weight", "expert_w2_weight", "bias",
                     "load_running_sum"],
          defaults={"num_experts": 0, "top_k": 1, "hidden": 0,
                    "experts_held": (), "score_func": "sigmoid",
                    "route_norm": True, "route_scale": 1.0,
                    "load_balance_coeff": 0.0})
def moe(data, router_weight, expert_w1_weight, expert_w3_weight,
        expert_w2_weight, bias, load_running_sum, num_experts=0, top_k=1,
        hidden=0, experts_held=(), score_func="sigmoid", route_norm=True,
        route_scale=1.0, load_balance_coeff=0.0, _train=False):
    """The routed experts' part of a mixture-of-experts feed-forward
    (``parallel.moe.moe_layer``): route over ``num_experts``, add up the
    terms of the ``experts_held = (first, count)`` this device holds
    (default: all). Gated SiLU experts of width ``hidden``: ``expert_w1``
    and ``expert_w3`` (count, in, hidden), ``expert_w2`` (count, hidden,
    in).

    ``bias`` (num_experts,) is the router's selection bias and
    ``load_running_sum`` (5,) = (training steps, rows the held experts
    took, rows of the fullest held expert, chunks of the sorted order the
    layer ran, held rows past the first chunk; each summed over the
    steps): auxiliary states that a training step's forward pass writes,
    as batch-norm's moving statistics are. Returns ``(out, counts)``;
    ``counts`` is hidden."""
    from ..parallel.moe import moe_layer
    held = _moe_held(dict(experts_held=experts_held,
                          num_experts=router_weight.shape[0]))
    return moe_layer(data, router_weight, bias, expert_w1_weight,
                     expert_w3_weight, expert_w2_weight, int(top_k), held,
                     score_func, bool(route_norm), float(route_scale))


def _moe_held(params):
    held = as_tuple(params.get("experts_held")) or ()
    return (int(held[0]), int(held[1])) if held \
        else (0, int(params["num_experts"]))


def _moe_shapes(shapes, params):
    d, f = shapes[0][-1], int(params["hidden"])
    n, (_, count) = int(params["num_experts"]), _moe_held(params)
    return {1: (n, d), 2: (count, d, f), 3: (count, d, f),
            4: (count, f, d), 5: (n,), 6: (5,)}


def _moe_stateful_update(raw_inputs, raw_outputs, params):
    if not params.get("_train"):
        return {}
    from ..parallel.moe import bias_update, chunk_load
    counts = raw_outputs[1]
    first, count = _moe_held(params)
    rows = counts[first:first + count]
    # what the layer's loops ran: their own trip count, from the same counts
    tokens = raw_inputs[0].size // raw_inputs[0].shape[-1]
    chunks, overflow = chunk_load(rows, tokens * int(params["top_k"]),
                                  counts.shape[0])
    load = raw_inputs[6] + jnp.stack([
        jnp.ones((), rows.dtype), jnp.sum(rows), jnp.max(rows), chunks,
        overflow]).astype(_F32)
    return {5: bias_update(raw_inputs[5], counts,
                           float(params.get("load_balance_coeff", 0.0))),
            6: load}


def _publish_load(delta):
    """What ``load_running_sum`` grew by since it was last read, into the
    telemetry counters (``Executor.publish_aux_counters``)."""
    from .. import telemetry
    steps, held, fullest, chunks, overflow = (int(round(v)) for v in delta)
    if steps:
        telemetry.counter_inc("moe.steps", steps)
        telemetry.counter_inc("moe.rows_held", held)
        telemetry.counter_inc("moe.rows_max", fullest)
        telemetry.counter_inc("moe.chunks_run", chunks)
        telemetry.counter_inc("moe.rows_overflow", overflow)


@register("_contrib_TokenCrossEntropy", nin=3,
          arg_names=["data", "weight", "label"],
          defaults={"num_classes": 0, "block": 2048})
def token_cross_entropy(data, weight, label, num_classes=0, block=2048):
    """The output head and its loss in one: ``logits = data weight^T``
    over ``num_classes`` rows of ``weight``, and per position the
    cross-entropy of ``label`` (float32, shaped like ``label``). The
    step's held output is then (batch, T) and no (tokens, classes) tensor
    outlives the op: logits are made ``block`` positions at a time and
    made again for the gradient. As a loss head the op's gradient is that
    of the *sum* of its output (``rescale_grad`` makes it a mean)."""
    shape = label.shape
    x = data.reshape(-1, data.shape[-1])
    y = label.reshape(-1).astype(jnp.int32)
    n, blk = x.shape[0], int(block)

    @jax.checkpoint
    def part(xb, yb):
        logits = jax.lax.dot_general(xb, weight, (((1,), (1,)), ((), ())),
                                     preferred_element_type=_F32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        return lse - jnp.take_along_axis(logits, yb[:, None], axis=-1)[:, 0]

    if n <= blk or n % blk:
        return part(x, y).reshape(shape)
    loss = jax.lax.map(lambda xy: part(*xy),
                       (x.reshape(n // blk, blk, -1), y.reshape(-1, blk)))
    return loss.reshape(shape)


def _ce_shapes(shapes, params):
    return {1: (int(params["num_classes"]), shapes[0][-1]),
            2: tuple(shapes[0][:-1])}


def install():
    rms = get_op("_contrib_RMSNorm")
    rms.param_shape_infer = _rms_shapes
    rms.param_dtype_infer = _f32_inputs(1)
    m = get_op("_contrib_MoE")
    m.visible_outputs = 1
    m.aux_inputs = (5, 6)
    m.stateful_update = _moe_stateful_update
    m.param_shape_infer = _moe_shapes
    m.param_dtype_infer = _f32_inputs(5, 6)
    m.aux_counters = {6: _publish_load}
    ce = get_op("_contrib_TokenCrossEntropy")
    ce.param_shape_infer = _ce_shapes
    ce.param_dtype_infer = lambda in_types, params: {2: np.int32}


install()
