"""Mixture-of-experts feed-forward: a dropless top-k layer that is told
which experts it holds.

New-framework extension (SURVEY.md §2.3 TP/PP/SP/EP row): the reference
predates MoE. This is the body an ``ep`` mesh axis wraps: every device
routes its tokens over ALL ``num_experts`` (the router, its selection
bias, the top-k, the normalisation over all k selected scores and the
scale keep their published form) and computes the terms of the sum that
its own experts ``[first, first + count)`` give. The other terms are
another device's; on one chip the layer runs without the exchange, and
nothing here stands in for it.

No token is dropped whatever the load. Static shapes come from sorting
the ``T x k`` selections by expert (selections of experts held elsewhere
sort to the end) and one grouped matrix product over the sorted rows
(``lax.ragged_dot``: a row costs one expert's product, whichever expert),
not from a capacity: a held expert takes as many rows as are routed to it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

__all__ = ["route", "held_experts_ffn", "moe_layer", "bias_update",
           "PARTITION_RULES"]

# The layer's layout as a partition-rule set the engine can apply
# (``PartitionRules(PARTITION_RULES)``): the router is tiny and
# replicated; expert weight stacks carry a leading expert axis sharded
# over ``ep``, each device's slice being its ``experts_held``.
PARTITION_RULES = [
    (r"router", P()),
    (r"expert_w[123]$", P("ep")),
    (r"expert", P("ep")),
]

_ACTS = {"silu": jax.nn.silu, "relu": jax.nn.relu}


def route(x, router_w, bias, top_k, score_func="sigmoid", route_norm=True,
          route_scale=1.0):
    """``(selected experts (T, k) int32, their weights (T, k) float32)``.

    Scores are float32: ``sigmoid`` or ``softmax`` of ``x router_w^T``
    over all experts. The top-k is taken of ``scores + bias`` (the
    selection bias steers the choice and carries no gradient); the weights
    are the *unbiased* scores of the chosen, divided by their sum
    (``route_norm``) and scaled."""
    logits = lax.dot_general(x, router_w, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    scores = jax.nn.sigmoid(logits) if score_func == "sigmoid" \
        else jax.nn.softmax(logits, axis=-1)
    biased = scores if bias is None else \
        scores + lax.stop_gradient(bias.astype(jnp.float32))
    _, sel = lax.top_k(lax.stop_gradient(biased), top_k)
    w = jnp.take_along_axis(scores, sel, axis=-1)
    if route_norm:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return sel.astype(jnp.int32), w * route_scale


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _spread(x, order, inv, k):
    """``(T, d) -> (T k, d)``: row r is token ``order[r] // k``'s. Its
    transpose is ``_collect``: both directions are row gathers (a
    scatter-add of 65,536 rows costs the chip several times a gather)."""
    return jnp.take(x, order // k, axis=0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _collect(y, order, inv, k):
    """``(T k, d) -> (T, d)``: token t's row is the sum of the rows its k
    selections were sorted to, ``inv[t k .. t k + k - 1]``, in float32."""
    rows = jnp.take(y, inv, axis=0).reshape(-1, k, y.shape[1])
    return jnp.sum(rows.astype(jnp.float32), axis=1).astype(y.dtype)


_spread.defvjp(lambda x, o, i, k: (_spread(x, o, i, k), (o, i)),
               lambda k, res, g: (_collect(g, *res, k), None, None))
_collect.defvjp(lambda y, o, i, k: (_collect(y, o, i, k), (o, i)),
                lambda k, res, g: (_spread(g, *res, k), None, None))


def held_experts_ffn(x, sel, w, w1, w3, w2, first=0, act="silu"):
    """The held experts' terms of ``sum_e w_e expert_e(x)``.

    ``x`` (T, d); ``sel``/``w`` (T, k) from ``route``; ``w1``/``w3``
    (count, d, f) and ``w2`` (count, f, d) are experts ``first ..
    first + count - 1`` (``w3=None``: no gate, ``act(x w1) w2``).
    Returns ``out`` (T, d) in x's type.

    The sorted buffers have ``T k`` rows, the most a step can route here;
    only the first ``sum(rows)`` are real. What the grouped product leaves
    in the others is undefined (on the chip it is not nought), so they are
    masked on the way in, which masks their gradient on the way back, and
    on the way out."""
    t, k = sel.shape
    count = w1.shape[0]
    with jax.named_scope("dispatch"):
        local = sel.reshape(-1) - first
        held = (local >= 0) & (local < count)
        key = jnp.where(held, local, count)       # elsewhere: sorts last
        order = jnp.argsort(key, stable=True).astype(jnp.int32)
        inv = jnp.zeros_like(order).at[order].set(
            jnp.arange(t * k, dtype=jnp.int32), unique_indices=True)
        rows = jnp.bincount(key, length=count + 1)[:count].astype(jnp.int32)
        real = (jnp.arange(t * k) < jnp.sum(rows))[:, None]
        xs = jnp.where(real, _spread(x, order, inv, k), 0)
        ws = jnp.take(w.reshape(-1), order)[:, None]
    with jax.named_scope("grouped"):
        h = _ACTS[act](_grouped(xs, w1, rows))
        if w3 is not None:
            h = h * _grouped(xs, w3, rows)
        y = _grouped(h.astype(x.dtype), w2, rows)
    with jax.named_scope("combine"):
        y = jnp.where(real, y.astype(jnp.float32) * ws, 0).astype(x.dtype)
        return _collect(y, order, inv, k)


def _grouped(lhs, rhs, group_sizes):
    """``lhs`` (m, k) rows sorted by group, ``rhs`` (g, k, n): row r of
    group e times ``rhs[e]``. Rows past the last group are undefined."""
    return lax.ragged_dot(lhs, rhs, group_sizes,
                          preferred_element_type=jnp.float32
                          ).astype(lhs.dtype)


def bias_update(bias, counts, coeff):
    """The selection bias after one training step (the auxiliary-loss-free
    balancing rule, as torchtitan's MoE applies it): experts chosen less
    than the mean go up by ``coeff``, the others down, and the update is
    centred. ``counts`` (num_experts,): positions whose top-k held e."""
    c = counts.astype(jnp.float32)
    delta = coeff * jnp.sign(jnp.mean(c) - c)
    return bias + (delta - jnp.mean(delta)).astype(bias.dtype)


def moe_layer(x, router_w, bias, w1, w3, w2, top_k, experts_held=None,
              score_func="sigmoid", route_norm=True, route_scale=1.0,
              act="silu"):
    """Route ``x`` (..., d) over ``router_w.shape[0]`` experts and add up
    the terms of the experts held here, ``experts_held = (first, count)``
    (default: all of them, ``w1.shape[0]``). Returns ``(out, counts)``:
    ``out`` shaped like ``x``; ``counts`` (num_experts,) int32, the
    positions whose top-k holds each expert, held here or not."""
    first, count = experts_held or (0, w1.shape[0])
    if count != w1.shape[0]:
        raise ValueError("experts_held counts %d experts, the weights %d"
                         % (count, w1.shape[0]))
    n = router_w.shape[0]
    flat = x.reshape(-1, x.shape[-1])
    with jax.named_scope("router"):
        sel, w = route(flat, router_w, bias, top_k, score_func, route_norm,
                       route_scale)
        counts = jnp.bincount(sel.reshape(-1), length=n).astype(jnp.int32)
    out = held_experts_ffn(flat, sel, w, w1, w3, w2, first, act)
    return out.reshape(x.shape), counts
