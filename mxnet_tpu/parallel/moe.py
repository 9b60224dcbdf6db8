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

The buffers of sorted rows have ``T k`` rows, the most a step can route
here, and hold real rows only as far as the step routes: the grouped
products skip the rest by themselves (and accumulate and hand back
float32), and every other pass over the sorted rows (dispatch, rounding a
product to the data's type, gate, weights, and their transposes) is a
loop over *chunks* of ``chunk_rows`` rows, the held experts' even share
of the selections, that runs the chunks that the held rows and a product's
tile of noughts after them fill (the next product's tile may read there),
counted up to whole stairs of three chunks (``chunk_load``). A router that
is still learning sends a device up to twice its even share within a few
hundred steps, more with every step and differently with every seed; all
of that is one stair, so a step's row passes cost the same whatever it
routes here, and only the products follow the load. A step that routes
every selection here runs all ``T k / chunk_rows``; what lies past the
chunks a step runs is never written and never read, and the combine takes
nothing from it. A layer that holds every expert has one chunk of ``T k``
rows.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from ..pallas.moe_combine import moe_combine

__all__ = ["route", "chunk_rows", "chunk_load", "held_experts_ffn",
           "moe_layer", "bias_update", "PARTITION_RULES"]

# The layer's layout as a partition-rule set the engine can apply
# (``PartitionRules(PARTITION_RULES)``): the router is tiny and
# replicated; expert weight stacks carry a leading expert axis sharded
# over ``ep``, each device's slice being its ``experts_held``.
PARTITION_RULES = [
    (r"router", P()),
    (r"expert_w[123]$", P("ep")),
    (r"expert", P("ep")),
]

_ACTS = {"silu": jax.nn.silu, "relu": jax.nn.relu,
         "relu2": lambda a: jnp.square(jax.nn.relu(a))}


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


#: rows of the grouped product's row tile where the chunks matter (the
#: compiler's kernel at the benchmark's shapes, (65,536 x 2,048) by
#: (16, 2,048, 1,024): its metadata lists ``T k / 512 + count - 1``
#: tiles, which ``tests/test_tpu_compile.py`` holds it to, there and at
#: the hybrid cell's). A chunk is a multiple of it and tiles start at its
#: multiples, so the tile that holds the last held row ends less than one
#: tile past it; and the products' widths are padded to multiples of it
#: (``held_experts_ffn``).
_TILE = 512

#: chunks in one stair of the row passes' trip count. The passes' cost
#: steps by what the trip count steps by, so a stair holds what a step may
#: route here while its router learns: over ``Module.fit`` windows of some
#: hundred steps a layer's held rows climb from about the even share to
#: 1.7 times it (``trinity_mini.fit``, 16 of 128 held) and, in the last
#: expert block of ``nemotron3_nano.fit`` (8 of 128), to a mean of 1.5 to
#: 2.0 times it over the window, the faster the later the layer and at a
#: pace that follows the seed. With a stair of one chunk those layers
#: crossed a step of the passes' cost at a time of the seed's choosing,
#: and the cell's rate followed the seed (PERF.md, PR 34). The chunk
#: itself stays one even share: passes over chunks three times as long
#: cost the chip a quarter more a row at 9,216 rows and four fifths more
#: at 24,576.
_STAIR = 3


def chunk_rows(selections, count, num_experts):
    """Rows of one chunk of the sorted order: the even share of
    ``selections`` (= T k) that ``count`` of ``num_experts`` experts take,
    rounded up to the grouped product's tile and never more than all of
    them (``count == num_experts``: the one chunk is the whole order)."""
    even = -(-selections * count // num_experts)
    return min(selections, -(-even // _TILE) * _TILE)


def chunk_load(rows, selections, num_experts):
    """``(chunks, overflow)`` of a layer whose held experts take ``rows``
    (count,) of the step's ``selections``: the chunks of the sorted order
    each row pass runs (the loops' trip count: as many whole stairs of
    ``_STAIR`` chunks as the held rows and one product tile after them,
    which is written as nought, reach into, and never more chunks than
    the order has) and the held rows past the first chunk. Both int32
    scalars."""
    size = chunk_rows(selections, rows.shape[0], num_experts)
    total = jnp.sum(rows.astype(jnp.int32))
    stair = _STAIR * size
    return (jnp.minimum(_STAIR * ((total + _TILE + stair - 1) // stair),
                        -(-selections // size)),
            jnp.maximum(total - size, 0))


def _put(buf, rows, chunk):
    return lax.dynamic_update_slice_in_dim(buf, rows, chunk * rows.shape[0],
                                           axis=0)


def _rows(fn, sort, bufs):
    """``fn`` over the sorted rows, a chunk at a time and only as far as
    the held rows reach: ``bufs`` are (rows, features) buffers of one row
    a sorted selection, ``fn`` maps a chunk of each to a tuple of such
    chunks, row by row. What a grouped product leaves past the held rows
    is undefined, and the next product's tile may read past them: so the
    rows past the held ones are written as nought to the end of the last
    chunk that runs, which lies a tile or more past them (``chunk_load``).
    The chunks after that are never written, nor read."""
    order, _, total, trips = sort
    size = order.shape[1]

    def chunk(c, outs):
        real = (c * size + jnp.arange(size) < total)[:, None]
        got = fn(*(lax.dynamic_slice_in_dim(b, c * size, size) for b in bufs))
        return tuple(_put(o, jnp.where(real, g, 0), c)
                     for o, g in zip(outs, got))

    like = jax.eval_shape(fn, *(jax.ShapeDtypeStruct(
        (size,) + b.shape[1:], b.dtype) for b in bufs))
    return lax.fori_loop(
        0, trips, chunk,
        tuple(lax.empty((order.size,) + o.shape[1:], o.dtype) for o in like))


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _spread(x, sort, k, back):
    """``(T, d) -> (chunks x size, d)``: sorted row r is token
    ``order[r] // k``'s, written for the chunks the held rows fill. Its
    transpose is ``back``, ``_collect`` for the rows: both directions are
    row gathers (a scatter-add of as many rows costs the chip several
    times a gather)."""
    return _rows(lambda at: (jnp.take(x, at[:, 0] // k, axis=0),), sort,
                 (sort[0].reshape(-1, 1),))[0]


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _collect(y, sort, k):
    """``(chunks x size, d) -> (T, d)``: token t's row is the sum, in
    float32, of the rows its k selections were sorted to, ``inv[t k ..
    t k + k - 1]``; a selection sorted past the held rows (its expert is
    held elsewhere) adds nought, and its row is not read (``moe_combine``
    reads the held rows alone)."""
    _, inv, total, _ = sort
    return moe_combine(y, inv, total, k)


def _picked(g, sort, k):
    """The weights' spread's transpose (``k`` is 1): ``(T k, 1)`` float32,
    selection s's entry sorted row ``inv[s]``'s, nought where that lies
    past the held rows. One column is no row for ``moe_combine`` to copy."""
    _, inv, total, _ = sort
    return jnp.where((inv < total)[:, None], jnp.take(g, inv, axis=0), 0)


_spread.defvjp(lambda x, sort, k, back: (_spread(x, sort, k, back), sort),
               lambda k, back, sort, g: (back(g, sort, k), None))
_collect.defvjp(lambda y, sort, k: (_collect(y, sort, k), sort),
                lambda k, sort, g: (_spread(g, sort, k, _collect), None))


def _grouped(lhs, rhs, group_sizes):
    """``lhs`` (m, k) rows sorted by group, ``rhs`` (g, k, n): row r of
    group e times ``rhs[e]``, accumulated and handed back in float32. Rows
    past the last group are undefined (on the chip they are not nought)."""
    return lax.ragged_dot(lhs, rhs, group_sizes,
                          preferred_element_type=jnp.float32)


#: ``_grouped``'s transpose for ``rhs``: a group's rows of ``lhs`` (m, k)
#: against the same rows of a cotangent (m, n)
_BY_ROWS = lax.RaggedDotDimensionNumbers(
    dot_dimension_numbers=(((0,), (0,)), ((), ())),
    lhs_ragged_dimensions=(0,), rhs_group_dimensions=())


def _grouped_back(lhs, rhs, group_sizes, g):
    """``_grouped``'s two transposes for the cotangent ``g`` (m, n), which
    comes in the operands' type (it is the cotangent of a product's
    *rounded* rows, so nothing is lost, and the products stay products of
    two narrow operands): the rows' in float32 as a product hands it back
    (it is rounded where its rows are next read), ``rhs``'s in ``rhs``'s
    type. These are the differentiation's own two products."""
    return (_grouped(g, jnp.swapaxes(rhs, 1, 2), group_sizes),
            lax.ragged_dot_general(
                lhs, g, group_sizes, _BY_ROWS,
                preferred_element_type=jnp.float32).astype(rhs.dtype))


def _round(v, dtype):
    """A product's float32 rows ``v`` in ``dtype``. The rounding is named
    before the type narrows: a bare ``astype`` of a chunk the compiler
    turns into a chunk of ``astype`` of the whole buffer, which is a pass
    over all ``T k`` rows ahead of the loop."""
    info = jnp.finfo(dtype)
    return lax.reduce_precision(v, info.nexp, info.nmant).astype(dtype)


def _gate(act, a1, *a3):
    h = _ACTS[act](a1)
    return h * a3[0] if a3 else h


def _weigh(y, ws):
    return (y.astype(jnp.float32) * ws).astype(y.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _experts(act, xs, ws, rows, sort, w1, w3, w2):
    """The sorted rows ``xs`` through their experts, times their weights
    ``ws``. A row's arithmetic: operands in ``xs``'s type, each product in
    float32 and rounded to that type, the weight applied in float32. The
    products run over the whole order (they skip what is not real), every
    other pass over the chunks the held rows fill. The pull-back is the
    differentiation's own, operation for operation, written out so that
    its passes run over those chunks too: left to the differentiation,
    each transposed product's rounding, the widening of each cotangent
    and the sum of the two cotangents of ``xs`` are passes over all
    ``T k`` rows."""
    return _experts_fwd(act, xs, ws, rows, sort, w1, w3, w2)[0]


def _experts_fwd(act, xs, ws, rows, sort, w1, w3, w2):
    def gate(*a):
        r = tuple(_round(v, xs.dtype) for v in a)
        return (_gate(act, *r),) + r

    def weigh(y, ws):
        y = _round(y, xs.dtype)
        return _weigh(y, ws), y

    # the rounded rows are what the pull-back reads: they are kept
    h, *r = _rows(gate, sort, tuple(
        _grouped(xs, u, rows) for u in (w1, w3) if u is not None))
    out, y = _rows(weigh, sort, (_grouped(h, w2, rows), ws))
    return out, (xs, ws, rows, sort, w1, w3, w2, tuple(r), h, y)


def _experts_bwd(act, res, g):
    xs, ws, rows, sort, w1, w3, w2, r, h, y = res

    def gate(gh, *r):
        return jax.vjp(functools.partial(_gate, act), *r)[1](
            _round(gh, xs.dtype))

    gy, gws = _rows(lambda g, y, ws: jax.vjp(_weigh, y, ws)[1](g), sort,
                    (g, y, ws))
    gh, gw2 = _grouped_back(h, w2, rows, gy)
    # a product's float32 is rounded, and added to the one before, as
    # soon as it is there: one float32 buffer of ``xs``'s size at a time
    gxs, gw = (), []
    for ga, u in zip(_rows(gate, sort, (gh,) + r), (w1, w3)):
        gx, gu = _grouped_back(xs, u, rows, ga)
        gxs = _rows(lambda v, *s: (sum(s, _round(v, xs.dtype)),), sort,
                    (gx,) + gxs)
        gw.append(gu)
    gxs, = gxs
    gw.append(None)
    return gxs, gws, None, None, gw[0], gw[1], gw2


_experts.defvjp(_experts_fwd, _experts_bwd)


# jitted by itself: a model's expert layers are of one shape, so the step
# traces, differentiates and lowers these passes once and calls them a layer
@functools.partial(jax.jit, static_argnames=("first", "act"))   # mxlint: disable=jit-site -- a body inside the caller's program (the fused step's card covers it), never a dispatch of its own
def held_experts_ffn(x, sel, w, counts, w1, w3, w2, first=0, act="silu"):
    """The held experts' terms of ``sum_e w_e expert_e(x)``.

    ``x`` (T, d); ``sel``/``w`` (T, k) from ``route`` and ``counts``
    (num_experts,) int32, the selections of each of the router's experts
    (``moe_layer`` counts them; its length is what the held share is of);
    ``w1``/``w3`` (count, d, f) and ``w2`` (count, f, d) are experts
    ``first .. first + count - 1`` (``w3=None``: no gate, ``act(x w1)
    w2``). Returns ``out`` (T, d) in x's type.

    The selections are sorted by expert, those held elsewhere last; the
    first ``sum(counts[first:first + count])`` sorted rows are real. The
    grouped products skip the others themselves; every other pass over
    the sorted rows (dispatch, the products' rounding to x's type, gate,
    weights) is made ``chunk_rows(T k, count, num_experts)`` rows at a
    time over the chunks ``chunk_load`` counts, and the combine takes
    nothing from a row past the real ones. Past those chunks a buffer
    holds whatever the memory held: no pass reads it."""
    t, k = sel.shape
    count, d, f = w1.shape
    rows = counts[first:first + count]
    # the grouped product's kernel tiles its widths too: at widths its
    # tile does not divide (2,688 x 1,856) a product costs the chip three
    # to five times what it costs at the next multiple (3,072 x 2,048,
    # a quarter more arithmetic), and three times as much a further row.
    # Noughts change no sum: a padded input feature meets a nought weight,
    # a padded hidden unit is act(0) = 0, a padded output is cut off again
    wide = (-d % _TILE, -f % _TILE)
    if any(wide):
        x = jnp.pad(x, ((0, 0), (0, wide[0])))
        w1, w3 = (u if u is None else
                  jnp.pad(u, ((0, 0), (0, wide[0]), (0, wide[1])))
                  for u in (w1, w3))
        w2 = jnp.pad(w2, ((0, 0), (0, wide[1]), (0, wide[0])))
    size = chunk_rows(t * k, count, counts.shape[0])
    with jax.named_scope("dispatch"):
        local = sel.reshape(-1) - first
        held = (local >= 0) & (local < count)
        key = jnp.where(held, local, count)       # elsewhere: sorts last
        order = jnp.argsort(key, stable=True).astype(jnp.int32)
        inv = jnp.zeros_like(order).at[order].set(
            jnp.arange(t * k, dtype=jnp.int32), unique_indices=True)
        # whole chunks, whatever the last one's start
        order = jnp.pad(order, (0, -(t * k) % size)).reshape(-1, size)
        sort = (order, inv, jnp.sum(rows),
                chunk_load(rows, t * k, counts.shape[0])[0])
        xs = _spread(x, sort, k, _collect)
        ws = _spread(w.reshape(-1, 1), sort, 1, _picked)
    with jax.named_scope("grouped"):
        y = _experts(act, xs, ws, rows, sort, w1, w3, w2)
    with jax.named_scope("combine"):
        return _collect(y, sort, k)[:, :d]


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
    out = held_experts_ffn(flat, sel, w, counts, w1, w3, w2, first, act)
    return out.reshape(x.shape), counts
