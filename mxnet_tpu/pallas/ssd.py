"""The within-chunk work of the chunked state-space recurrence (Mamba-2's
"SSD", Dao & Gu 2024) as Pallas TPU kernels: ``ops/lm.py``'s
``_contrib_SSD`` calls ``ssd_states`` and ``ssd_chunk`` for everything that
reads a chunk's positions, so that no ``(chunk, chunk)`` block is written
to or read from HBM and ``x`` is read where the model's projection left
it.

One program of the grid ``(batch, chunks / chunks a program, group)``
holds a few chunks of ``q`` positions of one group; per chunk ``x`` as a
``(q, r * head_dim)`` block of ``(b, T, heads * head_dim)`` (the group's
``r`` heads side by side), ``B`` and ``C`` as ``(q, state)`` blocks of ``(b,
T, groups * state)``, the row statistics lane-major (``cs``, the
cumulative sum of the log-decays inside the chunk, and ``delta`` as ``(r,
q)`` blocks of ``(b, chunks, heads, q)``) and the chunk's states as ``(r,
1, head_dim, state)`` blocks of ``(b, heads, chunks, head_dim, state)``.
Per head, with ``X = delta x``:

``ssd_states`` (``ssd_state_fwd`` / ``ssd_state_bwd``): the state a chunk
leaves, ``sum_j exp(cs_last - cs_j) X_j B_j^T`` (``ssd/state``).

``ssd_chunk`` (``ssd_chunk_fwd`` / ``ssd_chunk_bwd``): the chunk's own
positions, the state that enters it and the skip,

    G = C B^T                         once a group, float32
    L[i, j] = exp(cs_i - cs_j), i >= j (the mask on the exponent)
    Y = round(L o G) round(X) + exp(cs_i) C_i S_in^T + D x

(``ssd/diag`` + ``ssd/off``); the backward kernel makes ``L``, ``G`` and
``L o G`` again in VMEM: ``dM = dY X^T``, ``dX = M^T dY``, ``dG = sum_heads
dM o L``, ``dC = dG B``, ``dB = dG^T C``, ``d cs = rowsum(dM o L o G) -
colsum(dM o L o G)`` and the entering state's and the skip's terms.

Decays, exponents, ``L o G`` and every accumulator are float32; every
product takes operands of the data's type, rounded where the ``jax.numpy``
form these kernels replace rounded them, forward and backward (there the
cotangents ``dY``, ``dG``, ``exp(cs) dY`` and the states' are rounded
before their products, as XLA ran them). A result is rounded once, at the
kernel's output.

``head_dim`` (at most 128) and ``state`` that do not fit the lane tile are
padded with noughts by the entries; on the chip the chunk has to be a multiple of 128
and a group's heads a multiple of 8 (or all heads): anything else runs
interpreted, as everything does off the TPU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import _dot_precision, _use_interpret

__all__ = ["ssd_states", "ssd_chunk"]

_F32 = jnp.float32
LANES = 128


def _dot(a, b, contract):
    return lax.dot_general(a, b, (contract, ((), ())),
                           precision=_dot_precision(a.dtype),
                           preferred_element_type=_F32)


class _Tiles:
    """The lane tiles of a group's ``r`` heads of width ``p`` (a divisor
    of 128) side by side. The kernels work on a tile *transposed*,
    ``(128, q)``: a head is then ``p`` rows, the lane-major statistics
    broadcast over them, and a sum over a head's lanes is a sum over
    rows. (Spreading a statistic's column over a head's lanes, which the
    untransposed tile needs, cost more than all the rest of a kernel on
    the chip; a square transpose costs little.)"""

    def __init__(self, r, p, q):
        assert LANES % p == 0 and (r * p) % LANES == 0
        self.r, self.p, self.q = r, p, q
        self.count = r * p // LANES

    def heads(self, w):
        """``(head, its rows in the transposed tile)`` of tile ``w``."""
        first = w * LANES // self.p
        return [(first + k, slice(k * self.p, (k + 1) * self.p))
                for k in range(LANES // self.p)]

    def sl(self, w):
        """The lanes of tile ``w`` among the group's."""
        return slice(w * LANES, (w + 1) * LANES)

    def pick(self, w, hh, value):
        """``value`` (an untransposed ``(q, 128)`` tile) in head ``hh``'s
        lanes, 0 elsewhere."""
        if self.p == LANES:
            return value
        lane = lax.broadcasted_iota(jnp.int32, (self.q, LANES), 1)
        lo = hh * self.p - w * LANES
        return jnp.where((lane >= lo) & (lane < lo + self.p), value,
                         jnp.zeros((), value.dtype))

    def stack(self, w, ref, i):
        """The ``(128, n)`` tile of the heads' ``(p, n)`` states of chunk
        ``i`` in ``ref`` (a ``(1, r, chunks, p, n)`` block)."""
        return jnp.concatenate([ref[0, hh, i] for hh, _ in self.heads(w)], 0)

    def unstack(self, w, ref, i, value):
        for hh, rows in self.heads(w):
            ref[0, hh, i] = value[rows].astype(ref.dtype)


def _decay_block(cs):
    """``L[i, j] = exp(cs_i - cs_j)`` for ``i >= j``, else 0, from the
    lane-major ``cs`` ``(1, q)``: the mask on the exponent, so what is
    masked cannot overflow. ``cs_i`` down the rows is the transpose of
    ``cs`` broadcast over them."""
    q = cs.shape[-1]
    keep = lax.broadcasted_iota(jnp.int32, (q, q), 0) \
        >= lax.broadcasted_iota(jnp.int32, (q, q), 1)
    return jnp.exp(jnp.where(keep, jnp.broadcast_to(cs, (q, q)).T - cs,
                             -jnp.inf))


def _to_end(cs):
    """``exp(cs_last - cs_j)``: the decay from ``j`` to the chunk's end."""
    return jnp.exp(cs[:, -1:] - cs)


def _colsum(v):
    return jnp.sum(v, axis=0, keepdims=True)


def _each_chunk(kernel, skips=False):
    """``kernel(i, *refs, tiles=)`` for every chunk ``i`` of the program's
    blocks: the chunks are a leading dimension of every block. With
    ``skips`` the first ref is ``D`` (all heads, in SMEM), and the kernel
    gets the scalars of its group's heads in its place."""
    def run(*refs, tiles, chunks):
        if skips:
            first = pl.program_id(2) * tiles.r
            refs = ([refs[0][first + hh] for hh in range(tiles.r)],) \
                + refs[1:]
        if chunks == 1:
            kernel(0, *refs, tiles=tiles)
        else:
            lax.fori_loop(0, chunks,
                          lambda i, _: kernel(i, *refs, tiles=tiles), None)
    return run


# -- the state a chunk leaves -------------------------------------------------

@_each_chunk
def _state_fwd_kernel(i, x_ref, b_ref, cs_ref, dl_ref, own_ref, *,
                      tiles):
    bm, dl, end = b_ref[0, i], dl_ref[0, i], _to_end(cs_ref[0, i])
    for w in range(tiles.count):
        xt = x_ref[0, i, :, tiles.sl(w)].T.astype(_F32)        # (128, q)
        xs = jnp.concatenate(
            [((xt[rows] * dl[hh:hh + 1]) * end[hh:hh + 1]).astype(bm.dtype)
             for hh, rows in tiles.heads(w)], 0)
        tiles.unstack(w, own_ref, i, _dot(xs, bm, ((1,), (0,))))


@_each_chunk
def _state_bwd_kernel(i, x_ref, b_ref, cs_ref, dl_ref, down_ref,
                      dx_ref, db_ref, dcs_ref, ddl_ref, *, tiles):
    q = tiles.q
    bm, dl, end = b_ref[0, i], dl_ref[0, i], _to_end(cs_ref[0, i])
    dtype = bm.dtype
    last = lax.broadcasted_iota(jnp.int32, (1, q), 1) == q - 1
    db = jnp.zeros(bm.shape, _F32)
    for w in range(tiles.count):
        xt = x_ref[0, i, :, tiles.sl(w)].T.astype(_F32)        # (128, q)
        down = tiles.stack(w, down_ref, i).astype(dtype)       # (128, n)
        dxs = _dot(down, bm, ((1,), (1,)))                     # (128, q)
        xs, dx = [], []
        for hh, rows in tiles.heads(w):
            xd = xt[rows] * dl[hh:hh + 1]
            xs.append((xd * end[hh:hh + 1]).astype(dtype))
            dxd = dxs[rows] * end[hh:hh + 1]
            dx.append(dxd * dl[hh:hh + 1])
            ddl_ref[0, i, hh:hh + 1] = _colsum(dxd * xt[rows])
            # to_end_j = exp(cs_last - cs_j): cs_j takes the term's
            # negative, cs_last the sum of all of them
            v = _colsum(dxs[rows] * xd) * end[hh:hh + 1]
            dcs_ref[0, i, hh:hh + 1] = jnp.where(
                last, jnp.sum(v, axis=1, keepdims=True) - v, -v)
        db = db + _dot(jnp.concatenate(xs, 0).T, down, ((1,), (0,)))
        dx_ref[0, i, :, tiles.sl(w)] = jnp.concatenate(dx, 0).T.astype(
            dx_ref.dtype)
    db_ref[0, i] = db.astype(db_ref.dtype)


# -- a chunk's own positions and its entering state ---------------------------

@functools.partial(_each_chunk, skips=True)
def _chunk_fwd_kernel(i, d, x_ref, b_ref, c_ref, cs_ref, dl_ref, s_ref,
                      y_ref, *, tiles):
    bm, cm, cs, dl = b_ref[0, i], c_ref[0, i], cs_ref[0, i], dl_ref[0, i]
    dtype = bm.dtype
    start = jnp.exp(cs)                     # from the chunk's start to i
    g = _dot(cm, bm, ((1,), (1,)))                             # (q, q)
    for w in range(tiles.count):
        xt = x_ref[0, i, :, tiles.sl(w)].T.astype(_F32)        # (128, q)
        off = _dot(tiles.stack(w, s_ref, i), cm, ((1,), (1,)))  # (128, q)
        y = []
        for hh, rows in tiles.heads(w):
            lg = (_decay_block(cs[hh:hh + 1]) * g).astype(dtype)
            xw = (xt[rows] * dl[hh:hh + 1]).astype(dtype)      # (p, q)
            y.append(_dot(xw, lg, ((1,), (1,)))
                     + off[rows] * start[hh:hh + 1]
                     + d[hh] * xt[rows])
        y_ref[0, i, :, tiles.sl(w)] = jnp.concatenate(y, 0).T.astype(
            y_ref.dtype)


@functools.partial(_each_chunk, skips=True)
def _chunk_bwd_kernel(i, d, x_ref, b_ref, c_ref, cs_ref, dl_ref, s_ref,
                      dy_ref, dx_ref, db_ref, dc_ref, dcs_ref, ddl_ref,
                      dd_ref, ds_ref, *, tiles):
    q = tiles.q
    bm, cm, cs, dl = b_ref[0, i], c_ref[0, i], cs_ref[0, i], dl_ref[0, i]
    dtype = bm.dtype
    start = jnp.exp(cs)
    g = _dot(cm, bm, ((1,), (1,)))
    dg = jnp.zeros((q, q), _F32)
    dc = jnp.zeros(cm.shape, _F32)
    for w in range(tiles.count):
        xt = x_ref[0, i, :, tiles.sl(w)].T.astype(_F32)        # (128, q)
        dy = dy_ref[0, i, :, tiles.sl(w)]                      # (q, 128)
        dyt = dy.T.astype(_F32)                                # (128, q)
        s_in = tiles.stack(w, s_ref, i)                        # (128, n)
        off = _dot(s_in, cm, ((1,), (1,)))                     # (128, q)
        xw, dyo = [], []
        for hh, rows in tiles.heads(w):
            xw.append((xt[rows] * dl[hh:hh + 1]).astype(dtype))
            dyo.append((dyt[rows] * start[hh:hh + 1]).astype(dtype))
        # the entering state's term, y = start o (C S^T)
        dyo = jnp.concatenate(dyo, 0)                          # (128, q)
        tiles.unstack(w, ds_ref, i, _dot(dyo, cm, ((1,), (0,))))
        dc = dc + _dot(dyo.T, s_in, ((1,), (0,)))
        xw_rows = jnp.concatenate(xw, 0).T                     # (q, 128)
        dx = []
        for hh, rows in tiles.heads(w):
            lmat = _decay_block(cs[hh:hh + 1])
            dyh = dyt[rows].astype(dtype)                      # (p, q)
            dm = _dot(tiles.pick(w, hh, dy), xw_rows, ((1,), (1,)))
            dxd = _dot(dyh, (lmat * g).astype(dtype), ((1,), (0,)))
            dml = dm * lmat
            dg = dg + dml
            both = dml * g                  # dM o L o G: d of the exponent
            dcs_ref[0, i, hh:hh + 1] = _colsum(both.T) - _colsum(both) \
                + _colsum(dyt[rows] * off[rows]) * start[hh:hh + 1]
            ddl_ref[0, i, hh:hh + 1] = _colsum(dxd * xt[rows])
            dd_ref[0, i, hh:hh + 1] = _colsum(dyt[rows] * xt[rows])
            dx.append(dxd * dl[hh:hh + 1] + d[hh] * dyt[rows])
        dx_ref[0, i, :, tiles.sl(w)] = jnp.concatenate(dx, 0).T.astype(
            dx_ref.dtype)
    dc_ref[0, i] = (dc + _dot(dg.astype(dtype), bm, ((1,), (0,)))
                    ).astype(dc_ref.dtype)
    db_ref[0, i] = _dot(dg.T.astype(dtype), cm, ((1,), (0,))
                        ).astype(db_ref.dtype)


# -- the calls ----------------------------------------------------------------

#: the chunks one program takes (the largest of these that divides the
#: sequence's). On the chip at nemotron3_nano's shapes ``ssd_chunk_fwd``
#: read 0.73 ms at one, 0.65 at two, 0.64 at four and 0.63 at eight; the
#: other kernels did not tell them apart
CHUNKS_A_PROGRAM = (4, 2, 1)


class _Plan:
    """The grid (batch, chunks / chunks a program, group) and its block
    specs, from ``x`` ``(b, c, q, h p)``, ``B`` ``(b, c, q, g n)`` and
    ``cs`` ``(b, c, h, q)``: the chunks are a leading dimension of every
    block."""

    def __init__(self, x, bm, cs, n):
        b, c, q, hp = x.shape
        h = cs.shape[2]
        g = bm.shape[-1] // n
        r, p = h // g, hp // h
        m = next(m for m in CHUNKS_A_PROGRAM if c % m == 0)
        self.chunks = m
        self.grid = (b, c // m, g)
        self.tiles = _Tiles(r, p, q)
        self.states = (b, h, c, p, n)
        self.row = pl.BlockSpec((1, m, q, r * p), lambda i, j, k: (i, j, 0, k))
        self.bc = pl.BlockSpec((1, m, q, n), lambda i, j, k: (i, j, 0, k))
        self.stat = pl.BlockSpec((1, m, r, q), lambda i, j, k: (i, j, k, 0))
        self.state = pl.BlockSpec((1, r, m, p, n),
                                  lambda i, j, k: (i, k, j, 0, 0))

    def call(self, kernel, name, in_specs, out_specs, out_shape, *args):
        return pl.pallas_call(
            functools.partial(kernel, tiles=self.tiles, chunks=self.chunks),
            grid=self.grid,
            in_specs=in_specs, out_specs=out_specs, out_shape=out_shape,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel",) * 3),
            name=name, interpret=_use_interpret())(*args)


_shape = jax.ShapeDtypeStruct


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _states(x, delta, cs, bm, n):
    return _states_fwd(x, delta, cs, bm, n)[0]


def _states_fwd(x, delta, cs, bm, n):
    # the residuals are the inputs: a checkpoint segment makes them again
    # and has no use of its own for what a forward kernel wrote
    p = _Plan(x, bm, cs, n)
    own = p.call(_state_fwd_kernel, "ssd_state_fwd",
                 [p.row, p.bc, p.stat, p.stat], p.state,
                 _shape(p.states, _F32), x, bm, cs, delta)
    return own, (x, delta, cs, bm)


def _states_bwd(n, res, down):
    x, delta, cs, bm = res
    p = _Plan(x, bm, cs, n)
    dx, db, dcs, ddl = p.call(
        _state_bwd_kernel, "ssd_state_bwd",
        [p.row, p.bc, p.stat, p.stat, p.state],
        [p.row, p.bc, p.stat, p.stat],
        [_shape(x.shape, x.dtype), _shape(bm.shape, bm.dtype),
         _shape(cs.shape, _F32), _shape(cs.shape, _F32)],
        x, bm, cs, delta, down)
    return dx, ddl, dcs, db


_states.defvjp(_states_fwd, _states_bwd)


@jax.custom_vjp
def _chunk(d, x, delta, cs, bm, cm, s_in):
    return _chunk_fwd(d, x, delta, cs, bm, cm, s_in)[0]


_SCALARS = pl.BlockSpec(memory_space=pltpu.SMEM)


def _chunk_fwd(d, x, delta, cs, bm, cm, s_in):
    p = _Plan(x, bm, cs, s_in.shape[-1])
    y = p.call(_chunk_fwd_kernel, "ssd_chunk_fwd",
               [_SCALARS, p.row, p.bc, p.bc, p.stat, p.stat, p.state], p.row,
               _shape(x.shape, x.dtype), d, x, bm, cm, cs, delta, s_in)
    return y, (d, x, delta, cs, bm, cm, s_in)


def _chunk_bwd(res, dy):
    d, x, delta, cs, bm, cm, s_in = res
    p = _Plan(x, bm, cs, s_in.shape[-1])
    stat = _shape(cs.shape, _F32)
    dx, db, dc, dcs, ddl, dd, ds = p.call(
        _chunk_bwd_kernel, "ssd_chunk_bwd",
        [_SCALARS, p.row, p.bc, p.bc, p.stat, p.stat, p.state, p.row],
        [p.row, p.bc, p.bc, p.stat, p.stat, p.stat, p.state],
        [_shape(x.shape, x.dtype), _shape(bm.shape, bm.dtype),
         _shape(cm.shape, cm.dtype), stat, stat, stat,
         _shape(s_in.shape, s_in.dtype)],
        d, x, bm, cm, cs, delta, s_in, dy)
    return jnp.sum(dd, axis=(0, 1, 3)), dx, ddl, dcs, db, dc, ds


_chunk.defvjp(_chunk_fwd, _chunk_bwd)


# -- the entries: shapes that are not the tile's ------------------------------

def _pad_last(v, width):
    pad = width - v.shape[-1]
    return jnp.pad(v, ((0, 0),) * (v.ndim - 1) + ((0, pad),)) if pad else v


class _Fit:
    """The kernels' shapes for ``h`` heads of ``p`` in ``g`` groups, a
    state of ``n`` and chunks of ``q``: ``head_dim`` padded to a divisor of
    the lane tile at which a group's heads fill whole tiles, the state to
    whole tiles (noughts change no sum), and the chunks a leading
    dimension."""

    def __init__(self, h, p, n, g, q):
        if p > LANES:
            raise ValueError("head_dim %d: the kernels hold a head in one "
                             "lane tile of %d" % (p, LANES))
        self.h, self.p, self.n, self.g, self.q = h, p, n, g, q
        self.pp = 16
        while self.pp < p or (h // g * self.pp) % LANES:
            self.pp *= 2
        self.np = -(-n // LANES) * LANES

    def rows(self, x):
        """``(b, T, h p)`` -> ``(b, c, q, h pp)``."""
        b, t, _ = x.shape
        return _pad_last(x.reshape(b, t // self.q, self.q, self.h, self.p),
                         self.pp).reshape(b, t // self.q, self.q, -1)

    def unrows(self, y):
        b, c, q, _ = y.shape
        return y.reshape(b, c * q, self.h, self.pp)[..., :self.p
                                                    ].reshape(b, c * q, -1)

    def bc(self, v):
        """``(b, T, g n)`` -> ``(b, c, q, g np)``."""
        b, t, _ = v.shape
        return _pad_last(v.reshape(b, t // self.q, self.q, self.g, self.n),
                         self.np).reshape(b, t // self.q, self.q, -1)

    def states(self, s):
        """``(b, h, c, p, n)`` -> ``(b, h, c, pp, np)``."""
        return jnp.pad(s, ((0, 0),) * 3 + ((0, self.pp - self.p),
                                           (0, self.np - self.n)))


def ssd_states(x, delta, cs, bm, state):
    """The state each chunk's own positions leave, float32 ``(b, heads,
    chunks, head_dim, state)``: ``x`` ``(b, T, heads * head_dim)``;
    ``delta`` and ``cs`` (the cumulative sum of the log-decays inside each
    chunk) float32 and lane-major, ``(b, chunks, heads, chunk)``; ``bm``
    ``(b, T, groups * state)``; ``T = chunks * chunk``. Differentiable in
    all four."""
    h, q = cs.shape[2:]
    n = int(state)
    fit = _Fit(h, x.shape[-1] // h, n, bm.shape[-1] // n, q)
    own = _states(fit.rows(x), delta, cs, fit.bc(bm), fit.np)
    return own[..., :fit.p, :n]


def ssd_chunk(x, delta, cs, bm, cm, s_in, d):
    """The recurrence's output ``(b, T, heads * head_dim)`` in ``x``'s
    type, from each chunk's own positions, the state that enters it and
    the skip ``d x``: the operands of ``ssd_states``, ``cm`` like ``bm``,
    ``s_in`` ``(b, heads, chunks, head_dim, state)`` in ``x``'s type and
    ``d`` ``(heads,)`` float32. Differentiable in all seven; the
    backward's residuals are these inputs, nothing the forward kernel
    made."""
    h, _, p, n = s_in.shape[1:]
    fit = _Fit(h, p, n, bm.shape[-1] // n, cs.shape[-1])
    return fit.unrows(_chunk(d.astype(_F32), fit.rows(x), delta, cs,
                             fit.bc(bm), fit.bc(cm), fit.states(s_in)))
