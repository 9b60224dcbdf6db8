"""Blockwise (flash) attention as a Pallas TPU kernel.

New-framework extension beyond the 2017 reference (which predates
attention, SURVEY.md §5.7); this is the single-chip building block that
``parallel.ring_attention`` composes over the 'sp' mesh axis.

Design (TPU-first):
- grid over (batch*heads, q-blocks); each program owns a ``block_q``-row
  Q tile in VMEM and the device's whole local K/V block (VMEM-resident —
  ring attention keeps per-device K/V small, so one MXU matmul per tile
  beats a DMA'd kv-chunk loop).
- online softmax: running max ``m`` and denominator ``l`` per Q row, so
  the kernel can be chained across ring steps: ``flash_attention_carry``
  takes and returns the (o, m, l) accumulator, exactly the carry that
  rotates with ``ppermute``.
- causal masking by *global* positions (``q_offset``/``kv_offset``): the
  same kernel serves both the single-chip and the sequence-sharded case.
- ``interpret=True`` off-TPU so the unit suite runs on the CPU mesh.

Limit (TPU v5e, compiled for a described chip, jax 0.9.0): each program
holds the whole local K and V block plus a (block_q, S_kv) f32 score
tile in VMEM. (B4,H16,S2048,D128) and D64 bf16 compile, forward and
gradient. At (B1,H16,S8192,D128) bf16 the non-causal forward still
compiles, but the causal forward and the gradient are refused
(``RESOURCE_EXHAUSTED: Ran out of memory in memory space vmem``). Longer
sequences are what ``parallel.ring_attention``/``ulysses_attention``
are for: they keep the per-device S_kv short; a local block of 8192
reached through them on a TPU hits the same refusal
(tests/test_tpu_compile.py pins it).

Backward for the plain entry is a custom VJP: recompute probabilities
from the saved log-sum-exp one Q block at a time (lax.map), so peak
memory stays O(block_q * S) instead of O(S^2) — the flash backward
formulation, expressed in XLA.
"""
from __future__ import annotations

import functools
import math
import os

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["flash_attention", "flash_attention_carry"]

DEFAULT_BLOCK_Q = 128
# candidate Q-block sizes offered to the operator tuner (default first)
TUNE_BLOCKS_Q = (128, 256, 512)
NEG_INF = -1e30


def _use_interpret():
    return jax.default_backend() != "tpu"


def _dot_precision(dtype):
    """Mosaic refuses a bf16 x bf16 matmul at any contract precision but
    the default ("Bad lhs type"), so a process-wide
    ``jax_default_matmul_precision=highest`` (a parity-check habit) must
    not reach the kernel's bf16 dots. f32 operands keep the ambient one."""
    return None if dtype == jnp.float32 else lax.Precision.DEFAULT


def _resolve_block_q(q, k, causal, interpret):
    """``block_q=None`` -> measured choice per (shape, dtype, causal)
    signature via the operator tuner (mxnet_tpu.tuner ≙ reference
    operator_tune.h:37-202). Interpret mode (off-TPU) skips measurement —
    timings there say nothing about the MXU."""
    if interpret:
        return DEFAULT_BLOCK_Q
    b, h, s_q, d = q.shape
    s_kv = k.shape[2]
    effective = []
    for blk in TUNE_BLOCKS_Q:
        e = min(blk, max(s_q, 1))
        if e not in effective:
            effective.append(e)
    if len(effective) == 1:
        return effective[0]
    from ..tuner import tuned_choice

    def mk(blk):
        def thunk():
            qz = jnp.zeros((b, h, s_q, d), q.dtype)
            kz = jnp.zeros((b, h, s_kv, d), k.dtype)
            return _forward(qz, kz, kz, causal, 1.0 / math.sqrt(d), blk,
                            interpret)[0]
        return thunk

    key = "bh%d_sq%d_skv%d_d%d_%s_c%d" % (b * h, s_q, s_kv, d,
                                          jnp.dtype(q.dtype).name,
                                          int(causal))
    label = tuned_choice("flash_attention.block_q", key,
                         [(str(e), mk(e)) for e in effective], args=(q, k))
    return int(label)


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


def _forward(q, k, v, causal, scale, block_q, interpret):
    """(B, H, S, D) -> (out, lse). Single chip, whole sequence."""
    b, h, s_q, d = q.shape
    s_kv = k.shape[2]
    qf = q.reshape(b * h, s_q, d)
    kf = k.reshape(b * h, s_kv, d)
    vf = v.reshape(b * h, s_kv, d)
    o0 = jnp.zeros((b * h, s_q, d), jnp.float32)
    m0 = jnp.full((b * h, s_q), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b * h, s_q), jnp.float32)
    o, m, l = flash_attention_carry(qf, kf, vf, o0, m0, l0, 0, 0, causal,
                                    scale, block_q, interpret)
    out = o / jnp.maximum(l, 1e-30)[..., None]
    lse = m + jnp.log(jnp.maximum(l, 1e-30))
    return out.astype(q.dtype).reshape(b, h, s_q, d), lse.reshape(b, h, s_q)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def flash_attention(q, k, v, causal=False, scale=None,
                    block_q=None, interpret=None):
    """Exact attention, (B, H, S, D) layout, O(block_q * S) memory.

    Differentiable; the forward runs as a Pallas kernel on TPU (interpret
    mode elsewhere), the backward recomputes probabilities blockwise from
    the saved log-sum-exp. ``block_q=None`` (default) lets the operator
    tuner measure-and-cache the Q-block size per signature.
    """
    if interpret is None:
        interpret = _use_interpret()
    if block_q is None:
        block_q = _resolve_block_q(q, k, causal, interpret)
    out, _ = _forward(q, k, v, causal, scale if scale is not None
                      else 1.0 / math.sqrt(q.shape[-1]), block_q, interpret)
    return out


def _fwd(q, k, v, causal, scale, block_q, interpret):
    if interpret is None:
        interpret = _use_interpret()
    if block_q is None:
        block_q = _resolve_block_q(q, k, causal, interpret)
    scale = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    out, lse = _forward(q, k, v, causal, scale, block_q, interpret)
    return out, (q, k, v, out, lse)


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref,
                dq_ref, dk_ref, dv_ref, *, causal, scale, block_q):
    """One (bh, q-block) program of the flash backward: recompute p from
    the saved lse, then dv += p^T dO, ds = p*(dp - delta), dq = ds k,
    dk += ds^T q. dk/dv accumulate across the (sequential) q-block grid
    axis into constant-index output blocks — the TPU Pallas revisiting
    pattern."""
    i = pl.program_id(1)
    f32 = jnp.float32
    q = q_ref[0].astype(f32)           # (bq, D)
    k = k_ref[0].astype(f32)           # (S, D)
    v = v_ref[0].astype(f32)
    g = g_ref[0].astype(f32)           # (bq, D)
    lse = lse_ref[0]                   # (bq, 1) f32
    delta = delta_ref[0]               # (bq, 1) f32

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=f32) * scale
    if causal:
        s_kv = k.shape[0]
        q_pos = i * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, s_kv), 0)
        k_pos = jax.lax.broadcasted_iota(jnp.int32, (block_q, s_kv), 1)
        s = jnp.where(q_pos >= k_pos, s, NEG_INF)
    p = jnp.exp(s - lse)                                   # (bq, S)

    dv_c = jax.lax.dot_general(p, g, (((0,), (0,)), ((), ())),
                               preferred_element_type=f32)  # (S, D)
    dp = jax.lax.dot_general(g, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=f32)    # (bq, S)
    ds = p * (dp - delta) * scale
    dq = jax.lax.dot_general(ds, k, (((1,), (0,)), ((), ())),
                             preferred_element_type=f32)    # (bq, D)
    dk_c = jax.lax.dot_general(ds, q, (((0,), (0,)), ((), ())),
                               preferred_element_type=f32)  # (S, D)

    dq_ref[0] = dq

    @pl.when(i == 0)
    def _init():
        dk_ref[0] = jnp.zeros_like(dk_ref[0])
        dv_ref[0] = jnp.zeros_like(dv_ref[0])

    dk_ref[0] += dk_c
    dv_ref[0] += dv_c


def _bwd(causal, scale, block_q, interpret, res, g):
    if interpret is None:
        interpret = _use_interpret()
    use_xla = os.environ.get("MXTPU_FLASH_BWD", "") == "xla"
    if not use_xla:
        return _bwd_flash(causal, scale, block_q, interpret, res, g)
    return _bwd_xla(causal, scale, block_q, interpret, res, g)


def _bwd_flash(causal, scale, block_q, interpret, res, g):
    q, k, v, out, lse = res
    if block_q is None:
        # same tuner decision as the forward: the cache is keyed by the
        # identical signature, so the cached winner (or default) applies
        block_q = _resolve_block_q(q, k, causal, interpret)
    scale = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    b, h, s_q, d = q.shape
    s_kv = k.shape[2]
    f32 = jnp.float32
    bh = b * h
    qf = q.reshape(bh, s_q, d)
    kf = k.reshape(bh, s_kv, d)
    vf = v.reshape(bh, s_kv, d)
    gf = g.reshape(bh, s_q, d)
    of = out.reshape(bh, s_q, d)
    lf = lse.reshape(bh, s_q)

    block = min(block_q, max(s_q, 1))
    pad = (-s_q) % block
    qp, _ = _pad_q(qf, block)
    gp, _ = _pad_q(gf, block)
    op, _ = _pad_q(of, block)
    lsep = jnp.pad(lf, ((0, 0), (0, pad)), constant_values=-NEG_INF)
    delta = jnp.sum(gp.astype(f32) * op.astype(f32), -1)   # (BH, Sq')
    n_q = qp.shape[1] // block

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=0,
        grid=(bh, n_q),
        in_specs=[
            pl.BlockSpec((1, block, d), lambda b, i: (b, i, 0)),      # q
            pl.BlockSpec((1, s_kv, d), lambda b, i: (b, 0, 0)),       # k
            pl.BlockSpec((1, s_kv, d), lambda b, i: (b, 0, 0)),       # v
            pl.BlockSpec((1, block, d), lambda b, i: (b, i, 0)),      # g
            pl.BlockSpec((1, block, 1), lambda b, i: (b, i, 0)),      # lse
            pl.BlockSpec((1, block, 1), lambda b, i: (b, i, 0)),      # delta
        ],
        out_specs=[
            pl.BlockSpec((1, block, d), lambda b, i: (b, i, 0)),      # dq
            pl.BlockSpec((1, s_kv, d), lambda b, i: (b, 0, 0)),       # dk
            pl.BlockSpec((1, s_kv, d), lambda b, i: (b, 0, 0)),       # dv
        ],
    )
    kernel = functools.partial(_bwd_kernel, causal=causal, scale=scale,
                               block_q=block)
    dq, dk, dv = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((bh, qp.shape[1], d), f32),
            jax.ShapeDtypeStruct((bh, s_kv, d), f32),
            jax.ShapeDtypeStruct((bh, s_kv, d), f32),
        ],
        cost_estimate=pl.CostEstimate(
            flops=5 * bh * qp.shape[1] * s_kv * d,
            bytes_accessed=4 * (qp.size + kf.size + vf.size + gp.size),
            transcendentals=bh * qp.shape[1] * s_kv),
        interpret=interpret,
    )(qp, kf, vf, gp, lsep[..., None], delta[..., None])
    dq = dq[:, :s_q].reshape(b, h, s_q, d)
    return (dq.astype(q.dtype), dk.reshape(b, h, s_kv, d).astype(k.dtype),
            dv.reshape(b, h, s_kv, d).astype(v.dtype))


def _bwd_xla(causal, scale, block_q, interpret, res, g):
    q, k, v, out, lse = res
    if block_q is None:
        block_q = _resolve_block_q(q, k, causal, interpret)
    scale = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    b, h, s_q, d = q.shape
    s_kv = k.shape[2]
    block = min(block_q, s_q)
    pad = (-s_q) % block
    qp = jnp.pad(q, ((0, 0), (0, 0), (0, pad), (0, 0)))
    gp = jnp.pad(g, ((0, 0), (0, 0), (0, pad), (0, 0)))
    op = jnp.pad(out, ((0, 0), (0, 0), (0, pad), (0, 0)))
    # padded q rows get a large POSITIVE lse so p = exp(s - lse) -> 0
    # (NEG_INF here would give exp(+inf) -> NaN folded into dk/dv)
    lsep = jnp.pad(lse, ((0, 0), (0, 0), (0, pad)), constant_values=-NEG_INF)
    n_blk = qp.shape[2] // block

    # delta_i = rowsum(dO * O)
    delta = jnp.sum(gp.astype(jnp.float32) * op.astype(jnp.float32), -1)

    k_pos = jnp.arange(s_kv)

    def blk(i):
        def sl(x, ax=2):
            return lax.dynamic_slice_in_dim(x, i * block, block, axis=ax)
        qb, gb = sl(qp), sl(gp)
        lb = sl(lsep)
        db = sl(delta)
        s = jnp.einsum("bhqd,bhkd->bhqk", qb, k,
                       preferred_element_type=jnp.float32) * scale
        if causal:
            q_pos = i * block + jnp.arange(block)
            mask = q_pos[:, None] >= k_pos[None, :]
            s = jnp.where(mask, s, NEG_INF)
        p = jnp.exp(s - lb[..., None])                  # (b,h,block,S)
        dp = jnp.einsum("bhqd,bhkd->bhqk", gb, v,
                        preferred_element_type=jnp.float32)
        ds = p * (dp - db[..., None]) * scale
        dq = jnp.einsum("bhqk,bhkd->bhqd", ds, k)
        dk = jnp.einsum("bhqk,bhqd->bhkd", ds, qb)
        dv = jnp.einsum("bhqk,bhqd->bhkd", p, gb)
        return dq, dk, dv

    dqs, dks, dvs = lax.map(blk, jnp.arange(n_blk))
    dq = jnp.moveaxis(dqs, 0, 2).reshape(b, h, n_blk * block, d)[:, :, :s_q]
    dk = jnp.sum(dks, axis=0)
    dv = jnp.sum(dvs, axis=0)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


flash_attention.defvjp(_fwd, _bwd)
