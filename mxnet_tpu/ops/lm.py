"""Operators of today's decoder blocks (new-framework extension: the 2017
reference predates all of them): RMSNorm, rotary embedding, the SiLU
gate, causal grouped-query attention with an optional window, the
mixture-of-experts layer (gated, or ungated as ``_contrib_MoEUngated``)
with its selection bias as an auxiliary state, the Mamba-2 mixer's parts
(a causal depthwise convolution, the selective state-space recurrence in
its chunked dual form, the gated group norm), LFM2's gated short
convolution, Kimi Delta Attention's
recurrence (a gated delta rule with a decay a channel, chunked), and a
token-level cross-entropy head that holds a small output.

Layout: activations are ``(batch, T, features)``; heads lie side by side
in the feature axis (``heads * head_dim``), as ``FullyConnected`` with
``flatten=False`` produces them.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name

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
    ``q.k / sqrt(head_dim)``, softmax in float32. Queries and keys share
    ``head_dim``; the values' width a head is their own (``value``'s
    features over ``num_kv_heads``: latent attention's 128 beside keys of
    192) and the result is (batch, T, heads * value width). Forward and
    backward are the Pallas kernels of ``pallas.flash_attention``
    (interpreted off the TPU).

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
    b, t, _ = query.shape

    def heads(x, h):
        return x.reshape(b, t, h, -1).transpose(0, 2, 1, 3)

    o = flash_attention(heads(query, hq), heads(key, hkv), heads(value, hkv),
                        True, None, None, None, int(window) or None)
    return o.transpose(0, 2, 1, 3).reshape(b, t, -1)


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
    return _routed(data, router_weight, bias, expert_w1_weight,
                   expert_w3_weight, expert_w2_weight, top_k, experts_held,
                   score_func, route_norm, route_scale, "silu")


def _routed(data, router_weight, bias, w1, w3, w2, top_k, experts_held,
            score_func, route_norm, route_scale, act):
    from ..parallel.moe import moe_layer
    held = _moe_held(dict(experts_held=experts_held,
                          num_experts=router_weight.shape[0]))
    return moe_layer(data, router_weight, bias, w1, w3, w2, int(top_k), held,
                     score_func, bool(route_norm), float(route_scale), act)


@register("_contrib_MoEUngated", nin=6, nout=2,
          arg_names=["data", "router_weight", "expert_w1_weight",
                     "expert_w2_weight", "bias", "load_running_sum"],
          defaults={"num_experts": 0, "top_k": 1, "hidden": 0,
                    "experts_held": (), "score_func": "sigmoid",
                    "route_norm": True, "route_scale": 1.0,
                    "load_balance_coeff": 0.0, "act": "relu2"})
def moe_ungated(data, router_weight, expert_w1_weight, expert_w2_weight,
                bias, load_running_sum, num_experts=0, top_k=1, hidden=0,
                experts_held=(), score_func="sigmoid", route_norm=True,
                route_scale=1.0, load_balance_coeff=0.0, act="relu2",
                _train=False):
    """``_contrib_MoE`` with experts of two matrices and no gate:
    ``act(x W1) W2`` (``act``: a name of ``parallel.moe``'s table;
    ``relu2`` is ``relu(.)^2``). Routing, the held share, the auxiliary
    states and the outputs are ``_contrib_MoE``'s."""
    return _routed(data, router_weight, bias, expert_w1_weight, None,
                   expert_w2_weight, top_k, experts_held, score_func,
                   route_norm, route_scale, act)


def _moe_held(params):
    held = as_tuple(params.get("experts_held")) or ()
    return (int(held[0]), int(held[1])) if held \
        else (0, int(params["num_experts"]))


def _install_moe(name):
    """The hooks of a routed-expert op, by its inputs' names (the gated op
    has one matrix more than the ungated)."""
    m = get_op(name)
    at = {a: i for i, a in enumerate(m.arg_names)}
    bias, load = at["bias"], at["load_running_sum"]

    def shapes(shapes, params):
        d, f = shapes[0][-1], int(params["hidden"])
        n, (_, count) = int(params["num_experts"]), _moe_held(params)
        out = {at["router_weight"]: (n, d),
               at["expert_w2_weight"]: (count, f, d), bias: (n,), load: (5,)}
        out.update((at[a], (count, d, f)) for a in
                   ("expert_w1_weight", "expert_w3_weight") if a in at)
        return out

    def stateful_update(raw_inputs, raw_outputs, params):
        if not params.get("_train"):
            return {}
        from ..parallel.moe import bias_update, chunk_load
        counts = raw_outputs[1]
        first, count = _moe_held(params)
        rows = counts[first:first + count]
        # what the layer's loops ran: their own trip count, from the same
        # counts
        tokens = raw_inputs[0].size // raw_inputs[0].shape[-1]
        chunks, overflow = chunk_load(rows, tokens * int(params["top_k"]),
                                      counts.shape[0])
        grown = raw_inputs[load] + jnp.stack([
            jnp.ones((), rows.dtype), jnp.sum(rows), jnp.max(rows), chunks,
            overflow]).astype(_F32)
        return {bias: bias_update(
                    raw_inputs[bias], counts,
                    float(params.get("load_balance_coeff", 0.0))),
                load: grown}

    m.visible_outputs = 1
    m.aux_inputs = (bias, load)
    m.stateful_update = stateful_update
    m.param_shape_infer = shapes
    m.param_dtype_infer = _f32_inputs(bias, load)
    m.aux_counters = {load: _publish_load}


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


@register("_contrib_CausalConv1D", nin=3,
          arg_names=["data", "weight", "bias"],
          defaults={"kernel": 4, "act_type": "silu", "no_bias": False})
def causal_conv1d(data, weight, bias=None, kernel=4, act_type="silu",
                  no_bias=False):
    """A causal depthwise convolution along T of ``data`` (batch, T,
    channels): ``out[t] = bias + sum_k weight[:, k] * data[t - (kernel -
    1) + k]``, positions before the first counted as nought; ``weight``
    (channels, kernel), ``bias`` (channels,; none with ``no_bias``).
    ``act_type`` ``"silu"`` applies SiLU, ``None`` nothing. Sums in
    float32, the result in ``data``'s type."""
    y = _causal_taps(data, weight, int(kernel), None if no_bias else bias)
    if act_type == "silu":
        y = jax.nn.silu(y)
    elif act_type is not None:
        raise ValueError("act_type is 'silu' or None, not %r" % (act_type,))
    return y.astype(data.dtype)


def _causal_taps(data, weight, k, bias=None):
    """``bias + sum_j weight[:, j] * data[t - (k - 1) + j]`` over ``data``
    (batch, T, channels) in float32, positions before the first counted
    as nought. Written as ``k`` shifted products: one pass over the data
    for the compiler, where a grouped convolution of one channel a group
    is ``channels`` convolutions."""
    t = data.shape[1]
    x = jnp.pad(data.astype(_F32), ((0, 0), (k - 1, 0), (0, 0)))
    w = weight.astype(_F32)
    y = 0.0 if bias is None else bias.astype(_F32)
    return y + sum(x[:, i:i + t] * w[:, i] for i in range(k))


@register("_contrib_GatedShortConv", nin=2, arg_names=["data", "weight"],
          defaults={"kernel": 3})
def gated_short_conv(data, weight, kernel=3):
    """LFM2's short-convolution mixer between its two projections:
    ``data`` (batch, T, 3 d) is ``[B | C | x]`` side by side (the input
    projection's output, in that order) and the result (batch, T, d) is
    ``C * conv(B * x)``, ``conv`` causal and depthwise over T with
    ``weight`` (d, kernel), no bias and no activation (as
    ``_contrib_CausalConv1D``). Float32 throughout, the result in
    ``data``'s type. The layers a step runs are fixed by the symbol: the
    counters ``shortconv.runs`` and ``shortconv.steps`` are counted on the
    host (``_shortconv_count_runs``, ``_shortconv_count_steps``)."""
    d = data.shape[-1] // 3
    b, c, x = (data[..., i * d:(i + 1) * d].astype(_F32) for i in range(3))
    return (c * _causal_taps(b * x, weight, int(kernel))).astype(data.dtype)


def _shortconv_count_runs(out_shape, params, steps):
    """``steps`` training steps of one mixer into the telemetry counters
    (``Executor.publish_aux_counters``): ``shortconv.runs``, summed over
    the mixers."""
    from .. import telemetry
    telemetry.counter_inc("shortconv.runs", steps)


def _shortconv_count_steps(steps):
    """The training steps themselves, once for all mixers:
    ``shortconv.steps``."""
    from .. import telemetry
    telemetry.counter_inc("shortconv.steps", steps)


@register("_contrib_GatedRMSNorm", nin=3,
          arg_names=["data", "gate", "gamma"],
          defaults={"eps": 1e-5, "group_size": 0, "gate_act": "silu",
                    "norm_first": False})
def gated_rms_norm(data, gate, gamma, eps=1e-5, group_size=0,
                   gate_act="silu", norm_first=False):
    """``RMSNorm(data * silu(gate)) * gamma``: the statistics over each run
    of ``group_size`` features (0: all of them), ``gamma`` one scale a
    feature (Mamba-2's gated norm, the gate applied before the norm).
    With ``norm_first`` the norm comes first and the gate after it,
    ``RMSNorm(data) * gamma * act(gate)``, and ``gamma`` is one scale a
    feature *of a group*, (group_size,), shared by the groups (Kimi Delta
    Attention's output norm: a head is a group). ``gate_act`` is ``"silu"``
    or ``"sigmoid"``. Float32 throughout, the result in ``data``'s type."""
    shape = data.shape
    g = int(group_size) or shape[-1]
    act = {"silu": jax.nn.silu, "sigmoid": jax.nn.sigmoid}[gate_act]
    x, z = data.astype(_F32), act(gate.astype(_F32))
    if not norm_first:
        x = x * z
    x = x.reshape(shape[:-1] + (shape[-1] // g, g))
    r = jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    if norm_first:
        y = (x * r * gamma.astype(_F32)).reshape(shape) * z
    else:
        y = (x * r).reshape(shape) * gamma.astype(_F32)
    return y.astype(data.dtype)


def _decay_matrix(to, start, strict=False):
    """``L[..., i, j] = exp(to[..., i] - start[..., j])`` for ``i >= j`` (``i
    > j`` if ``strict``) and 0 elsewhere. The mask goes on the exponent:
    what is masked may overflow, and its gradient is then 0 and not NaN."""
    q = to.shape[-1]
    keep = jnp.tril(jnp.ones((q, q), bool), -1 if strict else 0)
    return jnp.exp(jnp.where(keep, to[..., :, None] - start[..., None, :],
                             -jnp.inf))


@register("_contrib_SSD", nin=7,
          arg_names=["data", "dt", "B", "C", "A_log", "dt_bias", "D"],
          defaults={"heads": 1, "head_dim": 0, "state": 0, "groups": 1,
                    "chunk": 128})
def ssd(data, dt, B, C, A_log, dt_bias, D, heads=1, head_dim=0, state=0,
        groups=1, chunk=128):
    """The selective state-space recurrence of a Mamba-2 mixer, per head h
    of ``heads`` (``heads / groups`` heads share one B and one C)::

        delta_t = softplus(dt_t + dt_bias)        a_t = -exp(A_log) delta_t
        S_t = exp(a_t) S_{t-1} + delta_t x_t B_t^T      (head_dim x state)
        y_t = S_t C_t + D x_t

    over ``data`` (batch, T, heads * head_dim), ``dt`` (batch, T, heads),
    ``B`` and ``C`` (batch, T, groups * state); ``A_log``, ``dt_bias``, ``D``
    (heads,). Computed in the chunked dual form (Dao & Gu 2024, "SSD"),
    never as a scan over T. With chunks of ``chunk`` positions and ``cs``
    the cumulative sum of ``a`` inside a chunk (``ssd/decay``, with
    ``delta``: elementwise over (batch, T, heads), handed on lane-major as
    (batch, chunks, heads, chunk)), everything that reads a chunk's
    positions runs in the Pallas kernels of ``pallas.ssd`` (interpreted
    off the TPU), one program a sequence, a few chunks and a group, and no
    ``(chunk, chunk)`` block exists outside them:

    - ``ssd/state`` (``ssd_states``: ``ssd_state_fwd`` / ``ssd_state_bwd``):
      each chunk leaves the state ``sum_j exp(cs_last - cs_j) delta_j x_j
      B_j^T``, (batch, heads, chunks, head_dim, state) float32;
    - ``ssd/pass`` (``jax.numpy``): the states that enter the chunks are
      those sums decayed over the chunks between, the same L-form over
      chunks: one product of (chunks, chunks) a head at full precision;
    - ``ssd/chunk`` (``ssd_chunk``: ``ssd_chunk_fwd`` / ``ssd_chunk_bwd``):
      a chunk's own positions give ``(L o (C B^T)) (delta x)`` with ``L[i,
      j] = exp(cs_i - cs_j)``, ``i >= j``, the mask on the exponent, and
      the entering state adds ``exp(cs_i) C_i S_in``; the backward kernel
      makes ``L``, ``C B^T`` and their product again on the chip.

    Decays, cumulative sums, exponents, ``L o (C B^T)`` and states are
    float32; the products take operands of ``data``'s type (``delta x``,
    ``L o (C B^T)``, the entering states and, backward, the cotangents are
    rounded to it) and accumulate in float32. The kernels' backward rules
    keep their inputs and nothing a forward kernel made, so a mirrored
    segment runs the forward kernels again only because what follows the
    node needs its output. ``head_dim`` and ``state`` that are not the
    kernels' tile are padded with noughts inside ``pallas.ssd``; a T that
    is no multiple of ``chunk`` is padded with positions that change no
    state; the chunk stays the configured one. On the chip ``chunk`` has to
    be a multiple of 128, ``heads / groups`` of 8 and ``head_dim`` at most
    128. The chunks a step
    computes are fixed by the shapes: the counters ``ssm.steps`` and
    ``ssm.chunks_run`` are counted on the host (``_ssd_count_steps``)."""
    h, n = int(heads), int(state)
    if data.shape[-1] != h * int(head_dim) or B.shape[-1] != int(groups) * n:
        raise ValueError(
            "data %s is not heads x head_dim = %d x %d wide, or B %s not "
            "groups x state = %d x %d" % (data.shape, h, int(head_dim),
                                          B.shape, int(groups), n))
    return _ssd(data, dt, B, C, A_log, dt_bias, D, h, n, int(chunk))


@functools.partial(jax.jit, static_argnums=(7, 8, 9))   # mxlint: disable=jit-site -- a body inside the caller's program (the fused step's card covers it), never a dispatch of its own
def _ssd(data, dt, B, C, A_log, dt_bias, D, h, n, q):
    """``_contrib_SSD``'s body, jitted by itself so that a model's mixers
    lower once."""
    from ..pallas.ssd import ssd_chunk, ssd_states
    b, t, _ = data.shape
    dtype = data.dtype
    with jax.named_scope("ssd/decay"):
        delta = jax.nn.softplus(dt.astype(_F32) + dt_bias.astype(_F32))
        a = -jnp.exp(A_log.astype(_F32)) * delta                  # (b, t, h)
        x = data
        pad = -t % q
        if pad:     # a = 0 and delta = 0: the state passes through
            a, delta, x, B, C = (jnp.pad(v, ((0, 0), (0, pad), (0, 0)))
                                 for v in (a, delta, x, B, C))
        c = (t + pad) // q
        cs = jnp.cumsum(a.reshape(b, c, q, h), axis=2)
        last = jnp.swapaxes(cs[:, :, -1], 1, 2)                   # (b, h, c)
        # the kernels take the row statistics lane-major: (b, c, h, q)
        delta, cs = jnp.swapaxes(delta.reshape(cs.shape), 2, 3), \
            jnp.swapaxes(cs, 2, 3)
    with jax.named_scope("ssd/state"):
        own = ssd_states(x, delta, cs, B, n)                # (b, h, c, p, n)
    with jax.named_scope("ssd/pass"):
        # into chunk z: chunk k's state for k < z, decayed over the chunks
        # between them (float32 states: the product at full precision)
        done = jnp.cumsum(last, axis=2)
        across = _decay_matrix(done - last, done, strict=True)
        s_in = jnp.einsum("bhzk,bhkpn->bhzpn", across, own,
                          precision=jax.lax.Precision.HIGHEST)
    with jax.named_scope("ssd/chunk"):
        y = ssd_chunk(x, delta, cs, B, C, s_in.astype(dtype), D)
    return y[:, :t]


def _ssd_count_steps(out_shape, params, steps):
    """``steps`` training steps of one recurrence into the telemetry
    counters (``Executor.publish_aux_counters``): its chunks are fixed by
    its shapes, so they are counted here on the host."""
    from .. import telemetry
    b, t = out_shape[:2]
    telemetry.counter_inc("ssm.steps", steps)
    telemetry.counter_inc(
        "ssm.chunks_run", steps * b * -(-t // int(params.get("chunk", 128))))


@register("_contrib_KDA", nin=7,
          arg_names=["query", "key", "value", "gate", "beta", "A_log",
                     "dt_bias"],
          defaults={"heads": 1, "chunk": 64, "sub": 16})
def kda(query, key, value, gate, beta, A_log, dt_bias, heads=1, chunk=64,
        sub=16):
    """Kimi Delta Attention's recurrence (Kimi Linear, arXiv:2510.26692): a
    gated delta rule whose decay is a channel's, per head h of ``heads``
    with keys of ``dk`` and values of ``dv`` features::

        q_t <- q_t / |q_t| / sqrt(dk)     k_t <- k_t / |k_t|   (L2, a head)
        g_t = -exp(A_log) softplus(gate_t + dt_bias)     alpha_t = exp(g_t)
        b_t = sigmoid(beta_t)
        S_t = (I - b_t k_t k_t^T) Diag(alpha_t) S_{t-1} + b_t k_t v_t^T
        o_t = S_t^T q_t                              (S: dk x dv, from 0)

    over ``query``, ``key``, ``gate`` (batch, T, heads * dk), ``value``
    (batch, T, heads * dv), ``beta`` (batch, T, heads); ``A_log`` (heads,),
    ``dt_bias`` (heads * dk,); ``|x|`` is ``sqrt(sum(x^2) + 1e-6)``. Computed
    in the chunked form, never as a scan over T. With chunks of ``chunk``
    positions and ``G`` the running sum of ``g`` inside a chunk:

    - ``kda/gate``: the norms, ``g``, ``b``, ``G`` (elementwise, float32);
    - ``kda/intra`` (``pallas.kda``'s ``kda_intra``: ``kda_intra_fwd`` /
      ``kda_intra_bwd``, interpreted off the TPU): the chunk's own blocks
      ``Akk[r, i] = sum_c k_r k_i exp(G_r - G_i)`` (``i < r``) and ``Aqk``
      (``q_r`` for ``k_r``, ``i <= r``), ``T = (I + Diag(b) Akk)^-1
      Diag(b)``, ``W = T (K e^G)``, ``U = T V``, one program a chunk and
      eight heads, and nothing of them but ``Aqk``, ``W`` and ``U`` leaves
      VMEM. No ``exp(-G)`` is ever formed (a fast head's would overflow
      over a chunk): inside a sub-block of ``sub`` positions the exponent
      is the two positions' own difference, masked before the
      exponential; between sub-blocks it is split at the later one's first
      position n into ``exp(G_r - G_n)`` and ``exp(G_n - G_i)``, both <=
      1, and the sum over channels is a product. The inverse is the
      doubling ``(I - A)(I + A^2)(I + A^4)...`` (``Akk`` is strictly lower,
      so it ends with ``A^(chunk/2)``), float32 at full precision; the
      backward kernel makes the blocks and the inverse again from the
      op's inputs. ``Aqk``, ``W`` and ``U`` carry the names
      ``kda_intra_aqk``, ``kda_intra_w``, ``kda_intra_u``, which a
      checkpoint segment keeps (``executor.MIRROR_KEEPS``): the pull-back
      of ``kda/state`` reads them, so the segment's backward makes
      ``kda/gate`` and ``kda/state`` again but not ``kda_intra_fwd``;
    - ``kda/state`` (``pallas.kda``'s ``kda_state``: ``kda_state_fwd`` /
      ``kda_state_bwd``): the state carried from chunk to chunk in VMEM,
      ``U~ = U - W S``, ``O = (Q e^G) S + tril(Aqk) U~``, ``S' =
      Diag(e^(G_last)) S + (K e^(G_last - G))^T U~``, one program a
      sequence and eight heads running down the chunks; ``O`` is written
      in ``query``'s type straight into the (batch, T, heads * dv) layout.
      Its backward kernel runs the chunks from the last and carries the
      state's cotangent; its residuals are its inputs and the entering
      states in ``query``'s type, which a checkpoint segment's first run
      does not write.

    ``g``, ``G``, ``b``, the blocks, ``T``, ``U``, ``U~`` and the states are
    float32; every product's operands are of ``query``'s type (``T``, ``K
    e^..``, ``Q e^..``, the entering state and ``U~`` rounded to it) with a
    float32 accumulator, but the doubling, which is float32 at full
    precision; ``Aqk`` and ``W`` leave the kernel in ``query``'s type. A T
    that is no multiple of ``chunk`` is padded with positions that change
    no state (``g`` 0, ``b`` 0). On the chip ``chunk`` and ``sub`` have
    to be multiples of 8. The
    chunks a step computes are fixed by the shapes: the counters
    ``kda.steps`` and ``kda.chunks_run`` are counted on the host
    (``_kda_count_steps``)."""
    h = int(heads)
    if query.shape[-1] % h or value.shape[-1] % h \
            or key.shape != query.shape or gate.shape != query.shape:
        raise ValueError(
            "query %s, key %s and gate %s are not alike, or not %d heads "
            "wide, or value %s is not" % (query.shape, key.shape, gate.shape,
                                          h, value.shape))
    if int(chunk) % int(sub):
        raise ValueError("chunk %d is no multiple of sub %d" % (chunk, sub))
    return _kda(query, key, value, gate, beta, A_log, dt_bias, h, int(chunk),
                int(sub))


def _l2(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def _kda_gate(query, key, value, gate, beta, A_log, dt_bias, h, q):
    """``kda/gate``: ``(q, k, v, G, b)`` chunk-major, (chunks, batch, heads,
    chunk, features): the kernels of ``kda/intra`` and ``kda/state`` take
    a chunk's block of it as it lies. ``q`` and ``k`` are
    normed (and ``q`` scaled), ``G`` is the running sum of ``g`` inside a
    chunk, ``b`` is ``sigmoid(beta)`` (.., chunk, 1); all float32 but
    ``v``."""
    b, t, _ = query.shape
    dk, dv = query.shape[-1] // h, value.shape[-1] // h
    pad = -t % q
    n = (t + pad) // q

    def chunks(x):      # (b, t, h, f) -> (n, b, h, q, f), padded with 0
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0))) if pad else x
        return x.reshape(b, n, q, h, -1).transpose(1, 0, 3, 2, 4)

    def heads(x):       # (b, t, h * f) -> (n, b, h, q, f) float32
        return chunks(x.reshape(b, t, h, -1)).astype(_F32)

    # the relayout moves the data's type, the float32 work comes after it
    g = -jnp.exp(A_log.astype(_F32))[:, None, None] * jax.nn.softplus(
        heads(gate) + dt_bias.astype(_F32).reshape(h, 1, dk))
    bt = jax.nn.sigmoid(heads(beta))
    if pad:     # g = 0 and b = 0: the state passes through
        live = (jnp.arange(n * q) < t).reshape(n, 1, 1, q, 1)
        g, bt = jnp.where(live, g, 0.0), jnp.where(live, bt, 0.0)
    return (_l2(heads(query)) * dk ** -0.5, _l2(heads(key)),
            chunks(value.reshape(b, t, h, dv)), jnp.cumsum(g, axis=3), bt)


@functools.partial(jax.jit, static_argnums=(7, 8, 9))   # mxlint: disable=jit-site -- a body inside the caller's program (the fused step's card covers it), never a dispatch of its own
def _kda(query, key, value, gate, beta, A_log, dt_bias, h, q, sub):
    """``_contrib_KDA``'s body, jitted by itself so that a model's layers
    lower once."""
    from ..pallas.kda import kda_intra, kda_state
    t = query.shape[1]
    with jax.named_scope("kda/gate"):
        qn, kn, v, G, bt = _kda_gate(query, key, value, gate, beta, A_log,
                                     dt_bias, h, q)
    with jax.named_scope("kda/intra"):
        aqk, w, u = kda_intra(qn, kn, v, G, bt[..., 0], sub)
        # what the pull-back of kda/state reads: a checkpoint segment keeps
        # them (executor.MIRROR_KEEPS) and runs kda_intra_fwd once
        aqk = checkpoint_name(aqk, "kda_intra_aqk")
        w = checkpoint_name(w, "kda_intra_w")
        u = checkpoint_name(u, "kda_intra_u")
    with jax.named_scope("kda/state"):
        o = kda_state(qn, kn, G, aqk, w, u)
    return o[:, :t]


def _kda_count_steps(out_shape, params, steps):
    """``steps`` training steps of one recurrence into the telemetry
    counters, as ``_ssd_count_steps`` (the names are written out: the lint
    holds ``telemetry.COUNTERS`` against the literal calls)."""
    from .. import telemetry
    b, t = out_shape[:2]
    telemetry.counter_inc("kda.steps", steps)
    telemetry.counter_inc(
        "kda.chunks_run", steps * b * -(-t // int(params.get("chunk", 64))))


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
    _install_moe("_contrib_MoE")
    _install_moe("_contrib_MoEUngated")
    conv = get_op("_contrib_CausalConv1D")
    conv.param_shape_infer = lambda shapes, params: {
        1: (shapes[0][-1], int(params.get("kernel", 4))),
        2: (shapes[0][-1],)}
    # the taps may come in float32 (a start plus learned offsets): the
    # result is of the data's type, and inference without shapes says so
    conv.param_dtype_infer = lambda in_types, params: {}
    short = get_op("_contrib_GatedShortConv")
    short.param_shape_infer = lambda shapes, params: {
        1: (shapes[0][-1] // 3, int(params.get("kernel", 3)))}
    short.param_dtype_infer = conv.param_dtype_infer
    short.step_counters = _shortconv_count_runs
    short.step_total = _shortconv_count_steps
    gated = get_op("_contrib_GatedRMSNorm")
    gated.param_shape_infer = lambda shapes, params: {
        2: (int(params.get("group_size", 0)) or shapes[0][-1]
            if params.get("norm_first") else shapes[0][-1],)}
    gated.param_dtype_infer = _f32_inputs(2)
    s = get_op("_contrib_SSD")
    s.param_shape_infer = lambda shapes, params: dict.fromkeys(
        (4, 5, 6), (int(params["heads"]),))
    s.param_dtype_infer = _f32_inputs(4, 5, 6)
    s.step_counters = _ssd_count_steps
    k = get_op("_contrib_KDA")
    k.param_shape_infer = lambda shapes, params: {
        5: (int(params["heads"]),), 6: (shapes[0][-1],)}
    k.param_dtype_infer = _f32_inputs(5, 6)
    k.step_counters = _kda_count_steps
    ce = get_op("_contrib_TokenCrossEntropy")
    ce.param_shape_infer = _ce_shapes
    ce.param_dtype_infer = lambda in_types, params: {2: np.int32}


install()
