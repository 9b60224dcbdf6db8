"""The chunked delta rule with a decay a channel (Kimi Delta Attention,
arXiv:2510.26692) as two Pallas TPU kernel pairs: ``ops/lm.py``'s
``_contrib_KDA`` calls ``kda_intra`` for everything ``kda/intra`` computes,
so that no block of exponentials, no ``(chunk, chunk)`` block and no step
of the inverse is written to or read from HBM, and ``kda_state`` for the
state carried from chunk to chunk and the output (below).

One program of the grid ``(chunks, batch, heads / heads a program)`` holds
one chunk of ``q`` positions of a few heads; per head the chunk's normed
``q`` and ``k``, the running sum ``G`` of the log-decays and ``v`` as
``(q, features)`` blocks of the chunk-major ``(chunks, b, heads, q,
features)`` arrays, and ``b`` lane-major as a ``(1, q)`` block of
``(chunks, b, heads, q)``. With sub-blocks of ``sub`` positions:

    A[r, i] = sum_c x_r[c] k_i[c] exp(G_r[c] - G_i[c])     (x = k or q)

``Akk`` (``i < r``) and ``Aqk`` (``i <= r``) are never formed with an
``exp(-G)``: inside a sub-block the exponent is the two positions' own
difference, masked before the exponential, made for one row of every
sub-block at a time (``(q, features)`` exponentials, never stored);
between sub-blocks it is split at the later one's first position ``n``
into ``exp(G_r - G_n)`` and ``exp(G_n - G_i)``, both <= 1, and the sum
over channels is a product with operands of the data's type. Then

    T = (I + Diag(b) strict(Akk))^-1 Diag(b)     float32, by doubling
    W = T (K e^G),  U = T V

``kda_intra_fwd`` hands back ``Aqk`` and ``W`` in the data's type (what
``kda_state`` takes them in) and ``U`` float32;
``kda_intra_bwd`` makes the blocks, their exponentials and the inverse
again in VMEM and takes the cotangents of the three to those of ``q``,
``k``, ``v``, ``G`` and ``b``. Exponents, blocks, the inverse and every
accumulator are float32; the products between sub-blocks and those of
``W`` and ``U`` take operands of the data's type (rounded where the
``jax.numpy`` form these kernels replace rounded them, forward and
backward); the doubling and its pull-back ``-inv^T g inv^T`` are float32
at full precision.

``kda_state_fwd`` runs the grid ``(batch, heads / heads a program,
chunks)``, the chunks in order, and carries each head's state ``S``
(``dk x dv``, float32, held transposed) in VMEM from one chunk to the
next, nought at the first:

    U~ = U - W S       O = (Q e^G) S + Aqk U~
    S <- Diag(e^(G_last)) S + (K e^(G_last - G))^T U~

``Q e^G`` and ``K e^(G_last - G)`` are made in VMEM; ``O`` is written in
the data's type straight into the op's ``(batch, T, heads x dv)`` layout,
a ``(chunk, heads a program x dv)`` block a program. Every product takes
operands of the data's type (``S``, ``U~`` and the decayed ``Q`` and
``K`` rounded to it, where the ``jax.numpy`` form these kernels replace
rounded them) and sums in float32. Where the pull-back will follow, it
also writes each chunk's entering state in the data's type: the
backward's residuals are these and the inputs. ``kda_state_bwd`` runs the
chunks from the last, carries the state's cotangent in VMEM and hands
back those of ``Q``, ``K``, ``G``, ``Aqk``, ``W`` and ``U``; the
cotangent of a value of the data's type is rounded to it. The decay's
cotangent reads the rounded entering state where the scan read the
float32 one. Under a checkpoint whose first run keeps no residual
(``optimize_remat``) that run writes the output alone.

On the chip a program takes 8 heads (all of them where the heads are no
multiple of 8), and the chunk and ``sub`` have to be multiples of 8;
off the TPU the kernels run interpreted.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import _dot_precision, _use_interpret

__all__ = ["kda_intra", "kda_state"]

_F32 = jnp.float32
_HIGHEST = lax.Precision.HIGHEST


def _dot(a, b, contract, precision=None):
    return lax.dot_general(a, b, (contract, ((), ())),
                           precision=precision or _dot_precision(a.dtype),
                           preferred_element_type=_F32)


def _round(x, dtype):
    """``x`` rounded to ``dtype`` and handed on as float32."""
    return x.astype(dtype).astype(_F32)


def _iota(shape, axis):
    return lax.broadcasted_iota(jnp.int32, shape, axis)


def _rows(x, sub, t):
    """Row ``t`` of every sub-block of ``x`` (q, f), spread over the
    sub-block's rows."""
    q, f = x.shape
    return jnp.concatenate(
        [jnp.broadcast_to(x[s + t:s + t + 1], (sub, f))
         for s in range(0, q, sub)], 0)


def _block_sums(x, sub):
    """The sums of ``x`` (q, f) over each sub-block's rows, spread over
    them."""
    q, f = x.shape
    return jnp.concatenate(
        [jnp.broadcast_to(jnp.sum(x[s:s + sub], 0, keepdims=True), (sub, f))
         for s in range(0, q, sub)], 0)


class _Chunk:
    """One head's chunk in VMEM: what both kernels make from ``q``, ``k``,
    ``G`` and ``b``."""

    def __init__(self, q, k, g, b, sub, dtype):
        self.q, self.k, self.g, self.sub, self.dtype = q, k, g, sub, dtype
        n = g.shape[0]
        self.n = n
        self.row, self.col = _iota((n, n), 0), _iota((n, n), 1)
        # a column's own row in its sub-block, and its sub-block's first row
        self.pos = _iota((n, 1), 0) % sub
        self.first = self.row - self.row % sub
        self.b_row = b                                            # (1, n)
        self.b_col = jnp.broadcast_to(b, (n, n)).T            # b_r on row r

    def own(self, t):
        """Row ``t`` of every sub-block: the exponentials ``e[i] =
        exp(G_r - G_i)`` for the sub-block's ``i <= r`` (0 elsewhere),
        ``r = first + t``, and ``k o e``."""
        gr = _rows(self.g, self.sub, t)
        e = jnp.exp(jnp.where(self.pos <= t, gr - self.g, -jnp.inf))
        return e, self.k * e

    def blocks(self, with_q=True):
        """``(Akk, Aqk)``, float32, 0 above the diagonal (and on it, for
        ``Akk``); ``Aqk`` is None without ``with_q``."""
        n, sub = self.n, self.sub
        kk = qk = jnp.zeros((n, n), _F32)        # transposed: [i, r]
        for t in range(sub):
            _, ke = self.own(t)
            at = self.first + t == self.col
            kk = kk + jnp.where(at, jnp.sum(ke * _rows(self.k, sub, t), 1,
                                            keepdims=True), 0.0)
            if with_q:
                qk = qk + jnp.where(at, jnp.sum(ke * _rows(self.q, sub, t),
                                                1, keepdims=True), 0.0)
        kk, qk = kk.T, qk.T
        if n > sub:
            # between sub-blocks: one product a later sub-block, [k; q] rows
            off = [_dot(*self.between(s)[:2], ((1,), (1,)))
                   for s in range(sub, n, sub)]
            zero = jnp.zeros((sub, n), _F32)
            kk = kk + jnp.concatenate([zero] + [o[:sub] for o in off], 0)
            qk = qk + jnp.concatenate([zero] + [o[sub:] for o in off], 0)
        return jnp.where(self.row > self.col, kk, 0.0), qk if with_q else None

    def between(self, s):
        """Rows ``s..s+sub`` against every earlier column, split at ``s``:
        ``(left, right, eL, eR)``, ``left = [k; q]_rows exp(G - G_s)``
        ``(2 sub, f)`` and ``right = k exp(G_s - G)`` (0 from ``s`` on)
        in the data's type."""
        sub = self.sub
        gs = self.g[s:s + 1]
        el = jnp.exp(self.g[s:s + sub] - gs)
        left = jnp.concatenate([self.k[s:s + sub] * el,
                                self.q[s:s + sub] * el], 0)
        er = jnp.exp(jnp.where(_iota((self.n, 1), 0) < s, gs - self.g,
                               -jnp.inf))
        return (left.astype(self.dtype), (self.k * er).astype(self.dtype),
                el, er)


def _inverses(a):
    """``(I + a_h)^-1`` for each strictly lower ``a_h`` of the list ``a``
    (float32, full precision): ``a_h`` is nilpotent, so the series is the
    finite product ``(I - a)(I + a^2)(I + a^4)...``. The heads' chains of
    products are independent and written side by side, so that the MXU
    takes one head's product while another's waits."""
    n = a[0].shape[0]
    eye = jnp.where(_iota((n, n), 0) == _iota((n, n), 1), 1.0, 0.0)
    y = [-x for x in a]
    inv = [eye + x for x in y]
    span = 2
    while span < n:
        y = [_dot(x, x, ((1,), (0,)), _HIGHEST) for x in y]
        inv = [i + _dot(i, x, ((1,), (0,)), _HIGHEST) for i, x in zip(inv, y)]
        span *= 2
    return inv


def _inverse_pullback(inv, g):
    """The cotangent of ``a`` from that of ``inv = (I + a)^-1``: ``-inv^T
    g inv^T``, float32 at full precision."""
    return -_dot(_dot(inv, g, ((0,), (0,)), _HIGHEST), inv, ((1,), (1,)),
                 _HIGHEST)


def _each_head(heads, body, *refs):
    """``body(hh, *refs)`` for every head of the program, in a loop."""
    lax.fori_loop(0, heads, lambda hh, _: body(hh, *refs), None)


def _chunk(refs, hh, sub):
    q_ref, k_ref, v_ref, g_ref, b_ref = refs
    return _Chunk(q_ref[0, 0, hh], k_ref[0, 0, hh], g_ref[0, 0, hh],
                  b_ref[0, 0, pl.ds(hh, 1)], sub, v_ref.dtype)


def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, aqk_ref, w_ref, u_ref,
                a_scr, *, heads, sub):
    ins = (q_ref, k_ref, v_ref, g_ref, b_ref)
    dtype = v_ref.dtype

    def blocks(hh, aqk_ref, a_scr):
        c = _chunk(ins, hh, sub)
        kk, qk = c.blocks()
        aqk_ref[0, 0, hh] = qk.astype(aqk_ref.dtype)
        a_scr[hh] = kk * c.b_col

    _each_head(heads, blocks, aqk_ref, a_scr)
    # the inverses of all the program's heads at once (their chains of
    # products interleave), then W and U
    for hh, inv in enumerate(_inverses([a_scr[hh] for hh in range(heads)])):
        tm = (inv * b_ref[0, 0, hh:hh + 1]).astype(dtype)
        kg = (k_ref[0, 0, hh] * jnp.exp(g_ref[0, 0, hh])).astype(dtype)
        w_ref[0, 0, hh] = _dot(tm, kg, ((1,), (0,))).astype(w_ref.dtype)
        u_ref[0, 0, hh] = _dot(tm, v_ref[0, 0, hh], ((1,), (0,))).astype(
            u_ref.dtype)


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, daqk_ref, dw_ref, du_ref,
                dq_ref, dk_ref, dv_ref, dg_ref, db_ref, kk_scr, inv_scr,
                da_scr, *, heads, sub):
    ins = (q_ref, k_ref, v_ref, g_ref, b_ref)
    dtype = v_ref.dtype

    def blocks(hh, kk_scr, inv_scr):
        c = _chunk(ins, hh, sub)
        kk_scr[hh] = c.blocks(with_q=False)[0]
        inv_scr[hh] = kk_scr[hh] * c.b_col

    _each_head(heads, blocks, kk_scr, inv_scr)
    for hh, inv in enumerate(_inverses([inv_scr[hh]
                                        for hh in range(heads)])):
        inv_scr[hh] = inv

    def products(hh, dk_ref, dv_ref, dg_ref, db_ref, da_scr):
        # W = T (K e^G), U = T V, T = inv Diag(b): the cotangents of the
        # rounded operands are rounded too
        k, v, b = k_ref[0, 0, hh], v_ref[0, 0, hh], b_ref[0, 0, pl.ds(hh, 1)]
        inv = inv_scr[hh]
        tm = (inv * b).astype(dtype)
        eg = jnp.exp(g_ref[0, 0, hh])
        kg = (k * eg).astype(dtype)
        dw, du = dw_ref[0, 0, hh], du_ref[0, 0, hh].astype(dtype)
        dt = _round(_dot(dw, kg, ((1,), (1,))), dtype) \
            + _round(_dot(du, v, ((1,), (1,))), dtype)
        dkg = _round(_dot(tm, dw, ((0,), (0,))), dtype)
        dv_ref[0, 0, hh] = _dot(tm, du, ((0,), (0,))).astype(dv_ref.dtype)
        dk_ref[0, 0, hh] = dkg * eg
        dg_ref[0, 0, hh] = dkg * (k * eg)
        db_ref[0, 0, pl.ds(hh, 1)] = jnp.sum(dt * inv, 0, keepdims=True)
        da_scr[hh] = dt * b                                  # of inv

    _each_head(heads, products, dk_ref, dv_ref, dg_ref, db_ref, da_scr)
    # inv = (I + a)^-1: da = -inv^T dinv inv^T, all the heads at once
    for hh in range(heads):
        da_scr[hh] = _inverse_pullback(inv_scr[hh], da_scr[hh])

    def blocks_back(hh, dq_ref, dk_ref, dg_ref, db_ref):
        c = _chunk(ins, hh, sub)
        n = c.n
        kk, da = kk_scr[hh], da_scr[hh]
        # a = Diag(b) kk
        db_ref[0, 0, pl.ds(hh, 1)] += jnp.sum((da * kk).T, 0, keepdims=True)
        dkk = jnp.where(c.row > c.col, da * c.b_col, 0.0)
        dqk = jnp.where(c.row >= c.col, daqk_ref[0, 0, hh].astype(_F32),
                        0.0)
        dk, dg = dk_ref[0, 0, hh], dg_ref[0, 0, hh]
        dq = jnp.zeros_like(c.q)
        # between sub-blocks: rows s.. against the columns before s
        for s in range(sub, n, sub):
            left, right, el, er = c.between(s)
            down = jnp.concatenate([dkk[s:s + sub], dqk[s:s + sub]], 0
                                   ).astype(dtype)              # (2 sub, n)
            dleft = _round(_dot(down, right, ((1,), (0,))), dtype)
            dright = _round(_dot(down, left, ((0,), (0,))), dtype)
            dlk, dlq = dleft[:sub] * el, dleft[sub:] * el

            def rows(x, s=s):       # x in rows s..s+sub, 0 elsewhere
                f = x.shape[1]
                return jnp.concatenate(
                    [jnp.zeros((s, f), _F32), x]
                    + [jnp.zeros((n - s - sub, f), _F32)] * (s + sub < n), 0)
            dk = dk + rows(dlk) + dright * er
            dq = dq + rows(dlq)
            lsum = rows(dlk * c.k[s:s + sub] + dlq * c.q[s:s + sub])
            rsum = dright * c.k * er
            # G_s sits in both exponents
            dgs = jnp.sum(rsum, 0, keepdims=True) \
                - jnp.sum(lsum, 0, keepdims=True)
            dg = dg + lsum - rsum + jnp.where(_iota((n, 1), 0) == s, dgs,
                                              0.0)
        # inside the sub-blocks, a row of each at a time
        dkk_t, dqk_t = dkk.T, dqk.T                              # [i, r]
        for t in range(sub):
            e, ke = c.own(t)
            at = c.first + t == c.col
            ckk = jnp.sum(jnp.where(at, dkk_t, 0.0), 1, keepdims=True)
            cqk = jnp.sum(jnp.where(at, dqk_t, 0.0), 1, keepdims=True)
            gr = ckk * _rows(c.k, sub, t) + cqk * _rows(c.q, sub, t)
            here = c.pos == t
            dk = dk + gr * e + jnp.where(here, _block_sums(ckk * ke, sub),
                                         0.0)
            dq = dq + jnp.where(here, _block_sums(cqk * ke, sub), 0.0)
            # the diagonal's exponent is 0 whatever G is: it adds nought
            p = jnp.where(c.pos < t, gr * ke, 0.0)
            dg = dg - p + jnp.where(here, _block_sums(p, sub), 0.0)
        dq_ref[0, 0, hh] = dq
        dk_ref[0, 0, hh] = dk
        dg_ref[0, 0, hh] = dg

    _each_head(heads, blocks_back, dq_ref, dk_ref, dg_ref, db_ref)


# -- the calls ----------------------------------------------------------------

#: heads one program takes, or all of them where they are not a multiple:
#: on the chip at kimi_linear's shapes 8 read 7.10 ms forward, 16 7.22, and
#: 16 left the backward kernel short of VMEM
HEADS_A_PROGRAM = 8


def _call(kernel, name, sub, scratch, outs, *args):
    n, b, h, q = args[4].shape
    hb = HEADS_A_PROGRAM if h % HEADS_A_PROGRAM == 0 else h

    def block(x):
        if x.ndim == 4:
            return pl.BlockSpec((1, 1, hb, q), lambda i, j, k: (i, j, k, 0))
        return pl.BlockSpec((1, 1, hb) + x.shape[3:],
                            lambda i, j, k: (i, j, k, 0, 0))
    return pl.pallas_call(
        functools.partial(kernel, heads=hb, sub=sub),
        grid=(n, b, h // hb),
        in_specs=[block(x) for x in args],
        out_specs=[block(x) for x in outs],
        out_shape=outs,
        scratch_shapes=[pltpu.VMEM((hb, q, q), _F32)] * scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",) * 3),
        name=name, interpret=_use_interpret())(*args)


_shape = jax.ShapeDtypeStruct


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _intra(q, k, v, g, b, sub):
    return _intra_fwd(q, k, v, g, b, sub)[0]


def _intra_fwd(q, k, v, g, b, sub):
    # the residuals are the inputs, which a checkpoint segment makes again;
    # what the kernel writes the segment keeps by name (ops.lm._kda) for
    # the state pair's pull-back, so this kernel runs once a step
    n, bb, h, c = b.shape
    lead = (n, bb, h, c)
    outs = (_shape(lead + (c,), v.dtype), _shape(k.shape, v.dtype),
            _shape(lead + (v.shape[-1],), _F32))
    out = _call(_fwd_kernel, "kda_intra_fwd", sub, 1, outs, q, k, v, g, b)
    return tuple(out), (q, k, v, g, b)


def _intra_bwd(sub, res, cot):
    q, k, v, g, b = res
    outs = (_shape(q.shape, _F32), _shape(k.shape, _F32),
            _shape(v.shape, v.dtype), _shape(g.shape, _F32),
            _shape(b.shape, _F32))
    return tuple(_call(_bwd_kernel, "kda_intra_bwd", sub, 3, outs,
                       q, k, v, g, b, *cot))


_intra.defvjp(_intra_fwd, _intra_bwd)


# -- the state from chunk to chunk, and the output ---------------------------

def _state_fwd_kernel(q_ref, k_ref, g_ref, aqk_ref, w_ref, u_ref, o_ref,
                      *rest, heads):
    # rest: the entering states' output where the pull-back needs them, and
    # the state S^T (heads, dv, dk) float32 carried from chunk to chunk
    s_ref, s_scr = rest if len(rest) == 2 else (None,) + rest
    dtype = o_ref.dtype
    dv = u_ref.shape[-1]

    @pl.when(pl.program_id(2) == 0)
    def _():
        s_scr[...] = jnp.zeros_like(s_scr)

    for hh in range(heads):
        st = s_scr[hh]
        sb = st.astype(dtype)
        if s_ref is not None:
            s_ref[0, 0, hh] = sb
        g = g_ref[0, 0, hh]
        last = g[-1:]
        ut = (u_ref[0, 0, hh] - _dot(w_ref[0, 0, hh], sb, ((1,), (1,)))
              ).astype(dtype)
        qe = (q_ref[0, 0, hh] * jnp.exp(g)).astype(dtype)
        o = _dot(qe, sb, ((1,), (1,))) \
            + _dot(aqk_ref[0, 0, hh], ut, ((1,), (0,)))
        o_ref[0, :, hh * dv:(hh + 1) * dv] = o.astype(dtype)
        kp = (k_ref[0, 0, hh] * jnp.exp(last - g)).astype(dtype)
        s_scr[hh] = st * jnp.exp(last) + _dot(ut, kp, ((0,), (0,)))


def _state_bwd_kernel(q_ref, k_ref, g_ref, aqk_ref, w_ref, u_ref, s_ref,
                      do_ref, dq_ref, dk_ref, dg_ref, daqk_ref, dw_ref, du_ref,
                      ds_scr, *, heads):
    # ds_scr: the cotangent of the state that leaves the chunk, transposed
    dtype = s_ref.dtype
    dv = u_ref.shape[-1]

    @pl.when(pl.program_id(2) == 0)
    def _():
        ds_scr[...] = jnp.zeros_like(ds_scr)

    for hh in range(heads):
        sb, w, a = s_ref[0, 0, hh], w_ref[0, 0, hh], aqk_ref[0, 0, hh]
        q, k, g = q_ref[0, 0, hh], k_ref[0, 0, hh], g_ref[0, 0, hh]
        n = g.shape[0]
        last = g[-1:]
        eg, ekp = jnp.exp(g), jnp.exp(last - g)
        ut = (u_ref[0, 0, hh] - _dot(w, sb, ((1,), (1,)))).astype(dtype)
        qe, kp = (q * eg).astype(dtype), (k * ekp).astype(dtype)
        do = do_ref[0, :, hh * dv:(hh + 1) * dv]
        ds = ds_scr[hh]
        dsb = ds.astype(dtype)
        # S' = Diag(e^last) S + kp^T U~, O = qe S + Aqk U~, U~ = U - W S:
        # the cotangent of a value of the data's type is of that type
        dkp = _round(_dot(ut, dsb, ((1,), (0,))), dtype)
        dut = _round(_round(_dot(kp, dsb, ((1,), (1,))), dtype)
                     + _round(_dot(a, do, ((0,), (0,))), dtype), dtype)
        dutb = dut.astype(dtype)
        dqe = _round(_dot(do, sb, ((1,), (0,))), dtype)
        daqk_ref[0, 0, hh] = _dot(do, ut, ((1,), (1,))).astype(daqk_ref.dtype)
        dw_ref[0, 0, hh] = (-_dot(dutb, sb, ((1,), (0,)))).astype(
            dw_ref.dtype)
        du_ref[0, 0, hh] = dut
        dsin = _round(_round(_dot(do, qe, ((0,), (0,))), dtype)
                      - _round(_dot(dutb, w, ((0,), (0,))), dtype), dtype)
        # the decays: e^last on the state, e^(last - G) on kp, e^G on qe
        tk = dkp * k * ekp
        dlast = jnp.exp(last) * jnp.sum(sb.astype(_F32) * ds, 0,
                                        keepdims=True) \
            + jnp.sum(tk, 0, keepdims=True)
        ds_scr[hh] = ds * jnp.exp(last) + dsin
        dq_ref[0, 0, hh] = dqe * eg
        dk_ref[0, 0, hh] = dkp * ekp
        dg_ref[0, 0, hh] = dqe * q * eg - tk + jnp.where(
            _iota((n, 1), 0) == n - 1, dlast, 0.0)


def _state_call(kernel, name, outs, back, *args):
    n, b, h, q, dk = args[0].shape
    dv = args[5].shape[-1]
    hb = HEADS_A_PROGRAM if h % HEADS_A_PROGRAM == 0 else h

    def z(k):                 # the backward runs down the chunks from the last
        return n - 1 - k if back else k

    def block(x):
        if x.ndim == 3:       # (batch, chunks x chunk, heads x dv)
            return pl.BlockSpec((1, q, hb * dv),
                                lambda i, j, k: (i, z(k), j))
        return pl.BlockSpec((1, 1, hb) + x.shape[3:],
                            lambda i, j, k: (z(k), i, j, 0, 0))
    return pl.pallas_call(
        functools.partial(kernel, heads=hb),
        grid=(b, h // hb, n),
        in_specs=[block(x) for x in args],
        out_specs=[block(x) for x in outs],
        out_shape=outs,
        scratch_shapes=[pltpu.VMEM((hb, dv, dk), _F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name=name, interpret=_use_interpret())(*args)


def _state_run(with_states, q, k, g, aqk, w, u):
    n, b, h, c, dk = q.shape
    dv = u.shape[-1]
    outs = (_shape((b, n * c, h * dv), w.dtype),)
    if with_states:
        outs += (_shape((n, b, h, dv, dk), w.dtype),)
    return _state_call(_state_fwd_kernel, "kda_state_fwd", outs, False,
                       q, k, g, aqk, w, u)


@jax.custom_vjp
def _state(q, k, g, aqk, w, u):
    # the output alone: what a checkpoint segment's first forward run needs
    return _state_run(False, q, k, g, aqk, w, u)[0]


def _state_fwd(q, k, g, aqk, w, u):
    o, s = _state_run(True, q, k, g, aqk, w, u)
    return o, (q, k, g, aqk, w, u, s)


def _state_bwd(res, do):
    q, k, g, aqk, w, u, s = res
    outs = (_shape(q.shape, _F32), _shape(k.shape, _F32),
            _shape(g.shape, _F32), _shape(aqk.shape, aqk.dtype),
            _shape(w.shape, w.dtype), _shape(u.shape, _F32))
    return tuple(_state_call(_state_bwd_kernel, "kda_state_bwd", outs, True,
                             q, k, g, aqk, w, u, s, do))


# where the residuals go unused (a checkpoint segment's first run) jax calls
# _state, which writes no entering state
_state.defvjp(_state_fwd, _state_bwd, optimize_remat=True)


def kda_intra(q, k, v, g, b, sub):
    """``(Aqk, W, U)`` of every chunk: ``q``, ``k``, ``g`` (the running
    sum of the log-decays inside each chunk) float32 and ``v`` chunk-major,
    ``(chunks, batch, heads, chunk, features)``; ``b`` float32 lane-major,
    ``(chunks, batch, heads, chunk)``; ``sub`` divides the chunk. ``Aqk``
    ``(.., chunk, chunk)`` (0 above the diagonal) and ``W`` ``(.., chunk,
    dk)`` in ``v``'s type, ``U`` ``(.., chunk, dv)`` float32.
    Differentiable in all five; the backward's residuals are these
    inputs."""
    return _intra(q.astype(_F32), k.astype(_F32), v, g.astype(_F32),
                  b.astype(_F32), int(sub))


def kda_state(q, k, g, aqk, w, u):
    """The output of every chunk, ``(batch, chunks x chunk, heads x dv)``
    in ``w``'s type, from ``q``, ``k``, ``g`` as ``kda_intra`` takes them
    and its ``(Aqk, W, U)``: the state enters the first chunk as nought.
    Differentiable in all six; the backward's residuals are these inputs
    and each chunk's entering state in ``w``'s type."""
    return _state(q.astype(_F32), k.astype(_F32), g.astype(_F32), aqk, w,
                  u.astype(_F32))
