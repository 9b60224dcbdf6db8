"""Blockwise (flash) attention as Pallas TPU kernels.

New-framework extension beyond the 2017 reference (which predates
attention, SURVEY.md §5.7). Two entries:

``flash_attention`` (single chip, differentiable): tiles over Q *and*
K/V. The grid is (batch, query head, Q block, K/V step); a program holds
one ``(block_q, D)`` Q tile, one ``(block_k, D)`` K and V tile and a
``(block_q, block_k)`` float32 score tile, with the online-softmax state
(running max, denominator, numerator) in VMEM scratch across the K/V
steps. Nothing in VMEM grows with the sequence.
- causal and windowed masks are a *band* of K/V blocks per Q block: the
  K/V axis of the grid is as long as the widest band (static), the block
  index is clamped into the band (a repeated index is not fetched again)
  and steps beyond it are skipped, so time follows the unmasked blocks.
  Blocks inside the band that no mask edge crosses take a path without
  the mask's compare and select.
- grouped-query heads: ``q`` is (B, Hq, S, D), ``k``/``v`` (B, Hkv, S, D)
  with Hq a multiple of Hkv; query head h reads K/V head h // (Hq/Hkv),
  no repeated copy of K/V in HBM.
- the backward is two kernels from the saved log-sum-exp (the flash
  backward): dQ over the same band, and dK/dV over the transposed band
  with the query heads of a group accumulated in scratch.
- operands stay in their own type (bf16 on the chip) with float32
  accumulation; softmax statistics are float32.

``flash_attention_carry`` (the building block ``parallel.ring_attention``
composes over the 'sp' mesh axis): grid over (batch*heads, Q blocks);
each program owns a ``block_q``-row Q tile and the device's whole local
K/V block, and takes and returns the online-softmax accumulator (o, m, l)
that rotates with ``ppermute``. Causal masking is by *global* positions
(``q_offset``/``kv_offset``).

``interpret=True`` off-TPU so the unit suite runs on the CPU mesh.

Limits (TPU v5e, compiled for a described chip, jax 0.9.0,
tests/test_tpu_compile.py): the plain entry compiles forward and gradient
at (B1, Hq32/Hkv4, S8192, D128) bf16, causal, with and without a 2,048
window, and at (B4, H16, S2048, D128). The carry entry still holds the
whole local K/V block and a (block_q, S_kv) f32 score tile in VMEM, so
a local block of 8,192 reached through the ring is refused
(``RESOURCE_EXHAUSTED ... vmem``): sequence parallelism keeps its local
block short.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["flash_attention", "flash_attention_carry"]

DEFAULT_BLOCK_Q = 128          # the carry entry's Q tile
#: the plain entry's Q and K/V tiles: (512, 512) float32 scores are 1 MB of
#: VMEM, and at S = 8,192 a tile is 1/16 of the sequence, so the causal
#: band wastes little on its diagonal
DEFAULT_BLOCK = 512
NEG_INF = -1e30


def _use_interpret():
    return jax.default_backend() != "tpu"


def _dot_precision(dtype):
    """Mosaic refuses a bf16 x bf16 matmul at any contract precision but
    the default ("Bad lhs type"), so a process-wide
    ``jax_default_matmul_precision=highest`` (a parity-check habit) must
    not reach the kernel's bf16 dots. f32 operands keep the ambient one."""
    return None if dtype == jnp.float32 else lax.Precision.DEFAULT


def _attn_kernel(scalars_ref, q_ref, k_ref, v_ref, o_in_ref, m_in_ref,
                 l_in_ref, o_ref, m_ref, l_ref, *, causal, scale, block_q):
    """One (bh, q-block) program: merge this K/V block into the online
    accumulator. scalars = [q_offset, kv_offset, kv_len]."""
    q_off = scalars_ref[0]
    kv_off = scalars_ref[1]
    kv_len = scalars_ref[2]

    q = q_ref[0]                       # (block_q, D)
    k = k_ref[0]                       # (S_kv, D)
    v = v_ref[0]
    s_kv = k.shape[0]

    scores = jax.lax.dot_general(
        q, k, dimension_numbers=(((1,), (1,)), ((), ())),
        precision=_dot_precision(q.dtype),
        preferred_element_type=jnp.float32) * scale     # (block_q, S_kv)

    qi = pl.program_id(1)
    q_pos = q_off + qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, s_kv), 0)
    k_pos = kv_off + jax.lax.broadcasted_iota(jnp.int32, (block_q, s_kv), 1)
    mask = k_pos < kv_len
    if causal:
        mask = jnp.logical_and(mask, q_pos >= k_pos)
    scores = jnp.where(mask, scores, NEG_INF)

    m_in = m_in_ref[0]                 # (block_q, 1)
    l_in = l_in_ref[0]
    o_in = o_in_ref[0]                 # (block_q, D)

    blk_max = jnp.max(scores, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_in, blk_max)
    corr = jnp.exp(m_in - m_new)
    p = jnp.exp(scores - m_new)
    p = jnp.where(mask, p, 0.0)
    l_new = l_in * corr + jnp.sum(p, axis=-1, keepdims=True)
    pv = jax.lax.dot_general(
        p.astype(v.dtype), v, dimension_numbers=(((1,), (0,)), ((), ())),
        precision=_dot_precision(v.dtype),
        preferred_element_type=jnp.float32)
    o_new = o_in * corr + pv

    o_ref[0] = o_new
    m_ref[0] = m_new
    l_ref[0] = l_new


def _carry_call(q, k, v, o, m, l, q_offset, kv_offset, kv_len, causal,
                scale, block_q, interpret):
    """Raw pallas_call on padded (BH, S, D) tensors. Accumulators are
    float32 (BH, Sq[, D])."""
    bh, s_q, d = q.shape
    n_q = s_q // block_q
    # accumulator stats ride as (BH, Sq, 1): unit lane dim keeps the
    # block shapes legal for Mosaic tiling
    m3 = m[..., None]
    l3 = l[..., None]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(bh, n_q),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, *_: (b, i, 0)),
            pl.BlockSpec((1, k.shape[1], d), lambda b, i, *_: (b, 0, 0)),
            pl.BlockSpec((1, k.shape[1], d), lambda b, i, *_: (b, 0, 0)),
            pl.BlockSpec((1, block_q, d), lambda b, i, *_: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i, *_: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i, *_: (b, i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, *_: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i, *_: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i, *_: (b, i, 0)),
        ],
    )
    scalars = jnp.asarray([q_offset, kv_offset, kv_len], jnp.int32)
    kernel = functools.partial(_attn_kernel, causal=causal, scale=scale,
                               block_q=block_q)
    s_kv = k.shape[1]
    flops = 4 * bh * s_q * s_kv * d
    o2, m2, l2 = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((bh, s_q, d), jnp.float32),
            jax.ShapeDtypeStruct((bh, s_q, 1), jnp.float32),
            jax.ShapeDtypeStruct((bh, s_q, 1), jnp.float32),
        ],
        cost_estimate=pl.CostEstimate(
            flops=flops,
            bytes_accessed=4 * (q.size + k.size + v.size + o.size),
            transcendentals=bh * s_q * s_kv),
        interpret=interpret,
    )(scalars, q, k, v, o, m3, l3)
    return o2, m2[..., 0], l2[..., 0]


def _pad_q(x, block_q):
    s = x.shape[1]
    pad = (-s) % block_q
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
    return x, s


def flash_attention_carry(q, k, v, o, m, l, q_offset=0, kv_offset=0,
                          causal=False, scale=None,
                          block_q=DEFAULT_BLOCK_Q, interpret=None):
    """Merge one K/V block into an online-softmax accumulator.

    q: (BH, Sq, D); k/v: (BH, Skv, D); o: (BH, Sq, D) f32 numerator;
    m/l: (BH, Sq) f32 running max / denominator. Returns updated
    (o, m, l) — the caller normalises ``o / l`` after the last block.
    """
    if interpret is None:
        interpret = _use_interpret()
    d = q.shape[-1]
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    block_q = min(block_q, max(q.shape[1], 1))
    qp, s_q = _pad_q(q, block_q)
    pad = qp.shape[1] - s_q
    if pad:
        o = jnp.pad(o, ((0, 0), (0, pad), (0, 0)))
        m = jnp.pad(m, ((0, 0), (0, pad)), constant_values=NEG_INF)
        l = jnp.pad(l, ((0, 0), (0, pad)))
    o2, m2, l2 = _carry_call(qp, k, v, o, m, l, q_offset, kv_offset,
                             kv_offset + k.shape[1], causal, scale,
                             block_q, interpret)
    if pad:
        o2, m2, l2 = o2[:, :s_q], m2[:, :s_q], l2[:, :s_q]
    return o2, m2, l2


# ---------------------------------------------------------------------------
# the plain entry: tiles over Q and K/V, banded, grouped-query
# ---------------------------------------------------------------------------

def _round_up(n, m):
    return -(-n // m) * m


class _Band:
    """Which K/V blocks a Q block attends, and the transposed question.
    Written with plain integer arithmetic so that the same methods serve
    Python ints (the static grid length) and traced program ids."""

    def __init__(self, n_q, n_k, bq, bk, causal, window, kv_len):
        self.n_q, self.n_k, self.bq, self.bk = n_q, n_k, bq, bk
        self.causal, self.window, self.kv_len = causal, window, kv_len
        self.k_steps = max(self.k_hi(i) - self.k_lo(i) + 1
                           for i in range(n_q))
        self.q_steps = max(self.q_hi(j) - self.q_lo(j) + 1
                           for j in range(n_k))

    @staticmethod
    def _min(a, b):
        return min(a, b) if isinstance(a, int) and isinstance(b, int) \
            else jnp.minimum(a, b)

    @staticmethod
    def _max(a, b):
        return max(a, b) if isinstance(a, int) and isinstance(b, int) \
            else jnp.maximum(a, b)

    def k_lo(self, i):
        if not self.window:
            return i * 0
        return self._min(self._max(i * self.bq - (self.window - 1), 0)
                         // self.bk, self.n_k - 1)

    def k_hi(self, i):
        if not self.causal:
            return i * 0 + self.n_k - 1
        return self._min(((i + 1) * self.bq - 1) // self.bk, self.n_k - 1)

    def q_lo(self, j):
        if not self.causal:
            return j * 0
        return self._min((j * self.bk) // self.bq, self.n_q - 1)

    def q_hi(self, j):
        if not self.window:
            return j * 0 + self.n_q - 1
        return self._min(((j + 1) * self.bk - 1 + self.window - 1)
                         // self.bq, self.n_q - 1)

    def crossed(self, i, j):
        """Whether an edge of the mask (the diagonal, the window's far
        side, the end of the real K/V rows) passes through block (i, j)."""
        q0, k0 = i * self.bq, j * self.bk
        hit = (k0 + self.bk) > self.kv_len
        if self.causal:
            hit = hit | ((k0 + self.bk - 1) > q0)
        if self.window:
            hit = hit | ((q0 + self.bq - 1 - k0) >= self.window)
        return hit

    def mask(self, i, j):
        q_pos = i * self.bq + lax.broadcasted_iota(
            jnp.int32, (self.bq, self.bk), 0)
        k_pos = j * self.bk + lax.broadcasted_iota(
            jnp.int32, (self.bq, self.bk), 1)
        m = k_pos < self.kv_len
        if self.causal:
            m = m & (q_pos >= k_pos)
        if self.window:
            m = m & (q_pos - k_pos < self.window)
        return m


def _dot(a, b, contract):
    return lax.dot_general(a, b, (contract, ((), ())),
                           precision=_dot_precision(a.dtype),
                           preferred_element_type=jnp.float32)


def _on_band(inside, crossed, body):
    """Run ``body(masked)`` if the step lies in the band: the masked form
    where a mask edge crosses the block, the bare one elsewhere."""
    pl.when(inside & crossed)(lambda: body(True))
    pl.when(inside & jnp.logical_not(crossed))(lambda: body(False))


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_s, l_s, acc_s, *,
                band, scale):
    i, j = pl.program_id(2), pl.program_id(3)
    kb = band.k_lo(i) + j

    @pl.when(j == 0)
    def _init():
        m_s[...] = jnp.full(m_s.shape, NEG_INF, jnp.float32)
        l_s[...] = jnp.zeros(l_s.shape, jnp.float32)
        acc_s[...] = jnp.zeros(acc_s.shape, jnp.float32)

    def body(masked):
        q, k, v = q_ref[0, 0], k_ref[0, 0], v_ref[0, 0]
        s = _dot(q, k, ((1,), (1,))) * scale             # (bq, bk)
        if masked:
            mask = band.mask(i, kb)
            s = jnp.where(mask, s, NEG_INF)
        m_prev = m_s[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        if masked:      # a row with nothing visible yet: exp(0) is not 0
            p = jnp.where(mask, p, 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_s[...] = l_s[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_s[...] = acc_s[...] * corr + _dot(p.astype(v.dtype), v,
                                              ((1,), (0,)))
        m_s[...] = m_new

    _on_band(kb <= band.k_hi(i), band.crossed(i, kb), body)

    @pl.when(j == band.k_steps - 1)
    def _done():
        l = jnp.maximum(l_s[...], 1e-30)
        o_ref[0, 0] = (acc_s[...] / l).astype(o_ref.dtype)
        lse_ref[0, 0] = m_s[...] + jnp.log(l)


def _dq_kernel(q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref, dq_ref,
               acc_s, *, band, scale):
    i, j = pl.program_id(2), pl.program_id(3)
    kb = band.k_lo(i) + j

    @pl.when(j == 0)
    def _init():
        acc_s[...] = jnp.zeros(acc_s.shape, jnp.float32)

    def body(masked):
        q, k, v, g = q_ref[0, 0], k_ref[0, 0], v_ref[0, 0], g_ref[0, 0]
        s = _dot(q, k, ((1,), (1,))) * scale
        if masked:
            s = jnp.where(band.mask(i, kb), s, NEG_INF)
        p = jnp.exp(s - lse_ref[0, 0])
        dp = _dot(g, v, ((1,), (1,)))
        ds = p * (dp - delta_ref[0, 0]) * scale
        acc_s[...] += _dot(ds.astype(k.dtype), k, ((1,), (0,)))

    _on_band(kb <= band.k_hi(i), band.crossed(i, kb), body)

    @pl.when(j == band.k_steps - 1)
    def _done():
        dq_ref[0, 0] = acc_s[...].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref, dk_ref,
                dv_ref, dk_s, dv_s, *, band, scale, group):
    jb, t = pl.program_id(2), pl.program_id(3)
    ib = band.q_lo(jb) + t % band.q_steps

    @pl.when(t == 0)
    def _init():
        dk_s[...] = jnp.zeros(dk_s.shape, jnp.float32)
        dv_s[...] = jnp.zeros(dv_s.shape, jnp.float32)

    def body(masked):
        q, k, v, g = q_ref[0, 0], k_ref[0, 0], v_ref[0, 0], g_ref[0, 0]
        s = _dot(q, k, ((1,), (1,))) * scale
        if masked:
            s = jnp.where(band.mask(ib, jb), s, NEG_INF)
        p = jnp.exp(s - lse_ref[0, 0])                    # (bq, bk)
        dv_s[...] += _dot(p.astype(g.dtype), g, ((0,), (0,)))
        dp = _dot(g, v, ((1,), (1,)))
        ds = p * (dp - delta_ref[0, 0]) * scale
        dk_s[...] += _dot(ds.astype(q.dtype), q, ((0,), (0,)))

    _on_band(ib <= band.q_hi(jb), band.crossed(ib, jb), body)

    @pl.when(t == group * band.q_steps - 1)
    def _done():
        dk_ref[0, 0] = dk_s[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_s[...].astype(dv_ref.dtype)


def _params(*semantics):
    return pltpu.CompilerParams(dimension_semantics=semantics)


def _pad_seq(x, block):
    pad = (-x.shape[2]) % block
    return jnp.pad(x, ((0, 0), (0, 0), (0, pad), (0, 0))) if pad else x


def _plan(q, k, causal, window, block_q, block_k):
    """Tile sizes and the band for ``q`` (B, Hq, Sq, D), ``k`` (B, Hkv,
    Skv, D)."""
    if window and not causal:
        raise ValueError("a window needs causal=True")
    if q.shape[1] % k.shape[1]:
        raise ValueError("%d query heads are no multiple of %d K/V heads"
                         % (q.shape[1], k.shape[1]))
    s_q, s_kv = q.shape[2], k.shape[2]
    bq = min(block_q or DEFAULT_BLOCK, _round_up(s_q, 8))
    bk = min(block_k or DEFAULT_BLOCK, _round_up(s_kv, 8))
    return _Band(_round_up(s_q, bq) // bq, _round_up(s_kv, bk) // bk, bq, bk,
                 bool(causal), int(window or 0), s_kv)


def _q_major_specs(band, group, d, dv):
    """Block specs of the grid (batch, query head, Q block, K/V step): a Q
    block's rows ``d`` wide (queries) and ``dv`` wide (the output and its
    cotangent), its per-row statistics, and the step's K/V block ``d``
    (keys) and ``dv`` (values) wide (the group's K/V head, the index
    clamped into the band)."""
    def kv_map(b_, h, i, j):
        return (b_, h // group,
                jnp.minimum(band.k_lo(i) + j, band.k_hi(i)), 0)

    def q_map(b_, h, i, j):
        return (b_, h, i, 0)

    return (pl.BlockSpec((1, 1, band.bq, d), q_map),
            pl.BlockSpec((1, 1, band.bq, dv), q_map),
            pl.BlockSpec((1, 1, band.bq, 1), q_map),
            pl.BlockSpec((1, 1, band.bk, d), kv_map),
            pl.BlockSpec((1, 1, band.bk, dv), kv_map))


def _forward(q, k, v, band, scale, interpret):
    """(out, lse) on padded inputs: ``out`` is (B, Hq, Sq', Dv), ``lse``
    (B, Hq, Sq', 1) float32."""
    b, hq, s_q, d = q.shape
    dv = v.shape[-1]
    bq, bk = band.bq, band.bk
    row, orow, stat, kv, vv = _q_major_specs(band, hq // k.shape[1], d, dv)
    steps = band.n_q * band.k_steps
    out = jax.ShapeDtypeStruct((b, hq, s_q, dv), q.dtype)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, band=band, scale=scale),
        grid=(b, hq, band.n_q, band.k_steps),
        in_specs=[row, kv, vv], out_specs=[orow, stat],
        out_shape=[out, jax.ShapeDtypeStruct((b, hq, s_q, 1), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, dv), jnp.float32)],
        compiler_params=_params("parallel", "parallel", "parallel",
                                "arbitrary"),
        cost_estimate=pl.CostEstimate(
            flops=2 * b * hq * steps * bq * bk * (d + dv),
            bytes_accessed=q.dtype.itemsize * (q.size + math.prod(out.shape)
                                               + k.size + v.size),
            transcendentals=b * hq * steps * bq * bk),
        name="flash_attention_fwd", interpret=interpret,
    )(q, k, v)


def _backward(q, k, v, g, lse, delta, band, scale, interpret):
    b, hq, s_q, d = q.shape
    hkv, d_v = k.shape[1], v.shape[-1]
    group = hq // hkv
    bq, bk = band.bq, band.bk
    row, orow, stat, kv, vv = _q_major_specs(band, group, d, d_v)
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, band=band, scale=scale),
        grid=(b, hq, band.n_q, band.k_steps),
        in_specs=[row, kv, vv, orow, stat, stat], out_specs=row,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        compiler_params=_params("parallel", "parallel", "parallel",
                                "arbitrary"),
        name="flash_attention_dq", interpret=interpret,
    )(q, k, v, g, lse, delta)

    def q_map(b_, h, j, t):
        return (b_, h * group + t // band.q_steps,
                jnp.minimum(band.q_lo(j) + t % band.q_steps, band.q_hi(j)),
                0)

    def kv_map(b_, h, j, t):
        return (b_, h, j, 0)

    qrow = pl.BlockSpec((1, 1, bq, d), q_map)
    grow = pl.BlockSpec((1, 1, bq, d_v), q_map)
    qstat = pl.BlockSpec((1, 1, bq, 1), q_map)
    krow = pl.BlockSpec((1, 1, bk, d), kv_map)
    vrow = pl.BlockSpec((1, 1, bk, d_v), kv_map)
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, band=band, scale=scale, group=group),
        grid=(b, hkv, band.n_k, group * band.q_steps),
        in_specs=[qrow, krow, vrow, grow, qstat, qstat],
        out_specs=[krow, vrow],
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        scratch_shapes=[pltpu.VMEM((bk, d), jnp.float32),
                        pltpu.VMEM((bk, d_v), jnp.float32)],
        compiler_params=_params("parallel", "parallel", "parallel",
                                "arbitrary"),
        name="flash_attention_dkv", interpret=interpret,
    )(q, k, v, g, lse, delta)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def flash_attention(q, k, v, causal=False, scale=None, block_q=None,
                    interpret=None, window=None, block_k=None):
    """Exact attention. ``q``: (B, Hq, Sq, D); ``k``: (B, Hkv, Skv, D);
    ``v``: (B, Hkv, Skv, Dv), ``Dv`` = ``D`` or not; the result is (B, Hq,
    Sq, Dv). Hq is a multiple of Hkv (query head h reads K/V head
    h // (Hq / Hkv)); the default ``scale`` is ``1 / sqrt(D)``. ``causal``: position i sees j <= i; ``window`` (with
    ``causal``): and only i - j < window, the position itself counted.

    Differentiable; forward and backward are Pallas kernels on the TPU
    (interpret mode elsewhere) whose VMEM use does not grow with S.

    The backward needs the output and the log-sum-exp of every row, and
    only the forward kernel can make them: the forward rule names both
    (``jax.ad_checkpoint.checkpoint_name``: ``flash_attention_out``,
    ``flash_attention_lse``), so a ``jax.checkpoint`` whose policy saves
    those names (a mirrored segment, ``executor.MIRROR_KEEPS``) holds them
    between forward and backward, (B, Hq, Sq, Dv) in ``q``'s type and (B,
    Hq, Sq) float32, and does not run the kernel again; ``q``, ``k``,
    ``v`` it makes again. Under any other checkpoint, and outside one, a name is
    the identity.
    """
    return _fwd(q, k, v, causal, scale, block_q, interpret, window,
                block_k)[0]


def _fwd(q, k, v, causal, scale, block_q, interpret, window, block_k):
    if interpret is None:
        interpret = _use_interpret()
    scale = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    band = _plan(q, k, causal, window, block_q, block_k)
    out, lse = _forward(_pad_seq(q, band.bq), _pad_seq(k, band.bk),
                        _pad_seq(v, band.bk), band, scale, interpret)
    # the two residuals only the kernel can produce carry a name
    # (``executor.MIRROR_KEEPS``): a checkpoint segment that saves by name
    # keeps them and does not run the kernel a second time. The log-sum-exp
    # crosses as (B, Hq, Sq'): a last dimension of 1 is padded to a whole
    # lane tile in HBM, 128 times its size.
    out = checkpoint_name(out[:, :, :q.shape[2]], "flash_attention_out")
    lse = checkpoint_name(lse[..., 0], "flash_attention_lse")
    return out, (q, k, v, out, lse)


def _bwd(causal, scale, block_q, interpret, window, block_k, res, g):
    q, k, v, out, lse = res
    if interpret is None:
        interpret = _use_interpret()
    scale = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    band = _plan(q, k, causal, window, block_q, block_k)
    # delta_i = rowsum(dO * O); padded rows have dO = 0, so every term
    # they would add to dK and dV is 0
    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32), -1,
                    keepdims=True)
    dq, dk, dv = _backward(
        _pad_seq(q, band.bq), _pad_seq(k, band.bk), _pad_seq(v, band.bk),
        _pad_seq(g.astype(q.dtype), band.bq), lse[..., None],
        _pad_seq(delta, band.bq), band, scale, interpret)
    return (dq[:, :, :q.shape[2]], dk[:, :, :k.shape[2]],
            dv[:, :, :v.shape[2]])


flash_attention.defvjp(_fwd, _bwd)
