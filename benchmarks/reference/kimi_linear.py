"""Plain reference for the ``kimi_linear`` hybrid decoder (Moonshot's Kimi
Linear, arXiv:2510.26692): forward pass, next-token cross-entropy,
``jax.grad``, MXNet's Adam and the router's bias update, in float32
``jax.numpy`` with matmul precision ``highest``. No kernel, no sort, no
chunked form: the delta-rule recurrence is a ``lax.scan`` over tokens
(taken a block of tokens at a time for the gradient, so that it fits),
attention a masked softmax a block of queries at a time, the routed
experts a dense sum over the held experts weighted by a one-hot of the
top-k.

The equations (``RMS(x) = x / sqrt(mean(x^2) + eps) * scale``; (A) marks
what the model's ``config.json`` does not say). ``h = E[ids]``; every
layer is ``h <- h + mixer(RMS(h))``, ``h <- h + ffn(RMS(h))``; the mixers:

- ``kda`` (H heads of D for keys and values, conv width K): ``q, k, v =
  silu(conv(u Wq)), silu(conv(u Wk)), silu(conv(u Wv))``, causal and
  depthwise without a bias, ``conv(x)[t] = sum_j w[:, j] x[t - (K - 1) +
  j]``; a head's ``q <- q / |q| / sqrt(D)``, ``k <- k / |k|`` with ``|x| =
  sqrt(sum x^2 + 1e-6)`` (A: the eps inside the root); ``g = -exp(A_log)
  softplus((u Wf1) Wf2 + dt_bias)`` a channel, ``alpha = exp(g)``; ``beta =
  sigmoid(u wb)`` a head; ``S_t = (I - beta_t k_t k_t^T) Diag(alpha_t)
  S_{t-1} + beta_t k_t v_t^T`` (D x D, from 0), ``o_t = S_t^T q_t``; ``o <-
  RMS_head(o; one scale of D for all heads) * sigmoid((u Wg1) Wg2)``; out ``o
  Wo``. ``A_log``, ``dt_bias`` and the taps come as offsets from starting
  values by index (``kda_start``, ``conv_start``).
- ``mla``: ``q = u Wq`` (H heads of 128 + 64); ``[c | k_pe] = u Wkva`` (512
  + 64); ``c <- RMS(c)``; ``[k_nope | v] = c Wkvb`` a head (128 + 128); a
  head's key is ``[k_nope | k_pe]``, the ``k_pe`` columns the same for
  every head; no rotation (``mla_use_nope``); position i sees j <= i; scores
  ``q.k / sqrt(192)``, softmax; out ``o Wo``.
- feed-forward: the first ``num_dense_layers`` layers ``(silu(m U1) * (m
  U3)) U2``; the others ``shared(m) + sum_{e in sel} w_e expert_e(m)`` with
  ``s = sigmoid(m Wr)``, ``sel = top_k(s + b)`` (the selection bias ``b``
  has no gradient), ``w = s[sel] / (sum s[sel] + 1e-20) * route_scale``,
  every expert and the shared one a SwiGLU. Of the sum only the terms of
  ``experts_held = (first, count)`` are computed: the share of one chip.
- ``loss = mean over positions of CE(RMS(h) Wout, next id)``.
- once a training step (A: afmoe's rule, no auxiliary loss): ``c_e`` =
  positions whose ``sel`` holds e; ``delta = load_balance_coeff *
  sign(mean(c) - c)``; ``b <- b + delta - mean(delta)``.
- Adam as MXNet's: ``g = rescale_grad * grad + wd * w``; ``m = b1 m + (1 -
  b1) g``; ``v = b2 v + (1 - b2) g^2``; ``w -= lr sqrt(1 - b2^t) / (1 -
  b1^t) * m / (sqrt(v) + eps)``; masters and moments float32.

It imports nothing of ``mxnet_tpu`` and takes nothing the program made:
parameters come in as a dict by the symbol's documented names
(``embed_weight``, ``l0_kda_wq_weight`` (out, in),
``l0_kda_conv_q_weight_offset`` (channels, K), ``l1_moe_expert_w1_weight``
(held, in, width), ``l1_moe_bias``, ...), made by the benchmark from the
seed, and may be host arrays.

``operand_round`` and ``state_dtype`` are for the control only: the
operands of every product (the recurrence's q, k and v among them) and on
the way back their gradients rounded to 8-bit floats, and the masters and
moments held in bf16: each the step below what the configuration states.
``fault`` plants one wrong mechanism (``FAULTS``) for the proof of the
limits.
"""
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

F32 = jnp.float32
#: where ``A`` (by head) and the step ``softplus(dt_bias)`` (by channel) start
A_START = (1.0, 16.0)
DT_START = (0.001, 0.1)
#: ``decay_per_head``: a head's 128 decays replaced by their mean (KDA read
#: as a gated delta net); ``no_delta``: ``I - beta k k^T`` as ``I``;
#: ``beta_one``: beta 1; ``no_qk_norm``: the L2 norms of q and k dropped;
#: ``gate_silu``: the output gate's sigmoid as SiLU; ``bf16_decay``: ``g``
#: and its exponential rounded to bfloat16; ``mla_scale``: scores over
#: ``sqrt(128)``; ``no_k_pe``: the shared key columns (and the queries'
#: beside them) left out; ``no_kva_norm``: the latent's norm dropped
FAULTS = ("decay_per_head", "no_delta", "beta_one", "no_qk_norm",
          "gate_silu", "bf16_decay", "mla_scale", "no_k_pe", "no_kva_norm")


def _fp8(x):
    """Round to e5m2 (two bits of mantissa) with one scale per tensor that
    puts the largest magnitude at the type's largest value."""
    top = float(jnp.finfo(jnp.float8_e5m2).max)
    scale = top / (jnp.max(jnp.abs(x)) + 1e-30)
    return (x * scale).astype(jnp.float8_e5m2).astype(x.dtype) / scale


@jax.custom_vjp
def fake_fp8(x):
    """The control's rounding: of the operand on the way forward and of its
    gradient on the way back."""
    return _fp8(x)


fake_fp8.defvjp(lambda x: (_fp8(x), None), lambda _, g: (_fp8(g),))

CONTROL = dict(operand_round=fake_fp8, state_dtype="bfloat16")


def _mm(x, w, rnd):
    """``x w^T`` for a weight stored (out, in)."""
    if rnd is not None:
        x, w = rnd(x), rnd(w)
    return x @ w.T


def rms(x, scale, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * scale


def _share(count, mul, mod):
    return (((jnp.arange(count, dtype=F32) * mul) % mod) + 0.5) / mod


def kda_start(c):
    """``(A_log (H,), dt_bias (H * D,))`` at their start: ``A`` evenly over
    1..16 by head; the step ``softplus(dt_bias)`` log-spaced over
    0.001..0.1 by channel, channel c of a head ``((37 c mod D) + 0.5) /
    D`` of the way; ``dt_bias`` is the step's inverse softplus."""
    h, dim = c["kda_num_heads"], c["kda_head_dim"]
    lo, hi = A_START
    t_lo, t_hi = DT_START
    at = jnp.arange(h, dtype=F32) / max(h - 1, 1)
    step = jnp.exp(math.log(t_lo) + _share(h * dim, 37.0, float(dim))
                   * (math.log(t_hi) - math.log(t_lo)))
    return jnp.log(lo + at * (hi - lo)), step + jnp.log(-jnp.expm1(-step))


def conv_start(channels, k):
    """(channels, k) over ``+-1 / sqrt(k)`` (PyTorch's ``Conv1d`` start,
    uniform), spread by index: entry i of the flattened weight is ``((487 i
    mod 1021) + 0.5) / 1021`` of the way."""
    bound = 1.0 / math.sqrt(k)
    return (_share(channels * k, 487.0, 1021.0) * (2 * bound) - bound
            ).reshape(channels, k)


def short_conv(x, offset, k):
    """``silu(conv(x))`` over ``x`` (T, channels)."""
    t = x.shape[0]
    w = conv_start(x.shape[1], k) + offset
    padded = jnp.pad(x, ((k - 1, 0), (0, 0)))
    return jax.nn.silu(sum(padded[i:i + t] * w[:, i] for i in range(k)))


def delta_rule(q, k, v, alpha, beta, block=128, delta=True):
    """``S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t
    v_t^T``, ``o_t = S_t^T q_t``, from ``S = 0``: ``q``, ``k``, ``alpha`` (T,
    H, Dk), ``v`` (T, H, Dv), ``beta`` (T, H); returns ``o`` (T, H, Dv). A
    scan over tokens; for the gradient a block of tokens is made again from
    the state that entered it. Without ``delta`` the state is not corrected
    by what it already holds of ``k_t``."""
    t, h, dk = q.shape
    dv = v.shape[-1]

    def token(s, a):
        qt, kt, vt, at, bt = a
        s = at[..., None] * s
        seen = jnp.einsum("hk,hkv->hv", kt, s) if delta else 0.0
        s = s + (bt[:, None] * kt)[..., None] * (vt - seen)[:, None, :]
        return s, jnp.einsum("hk,hkv->hv", qt, s)

    @jax.checkpoint
    def tokens(s, a):
        return lax.scan(token, s, a)

    blk = block if t % block == 0 else t
    _, o = lax.scan(tokens, jnp.zeros((h, dk, dv), F32), tuple(
        x.reshape((t // blk, blk) + x.shape[1:])
        for x in (q, k, v, alpha, beta)))
    return o.reshape(t, h, dv)


def _l2(x, eps=1e-6):
    return x * lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


def kda(u, p, pre, c, rnd, fault):
    h, dim, k = (c["kda_num_heads"], c["kda_head_dim"],
                 c["short_conv_kernel_size"])
    t = u.shape[0]

    def stream(s):
        return short_conv(_mm(u, p[pre + "w%s_weight" % s], rnd),
                          p[pre + "conv_%s_weight_offset" % s], k
                          ).reshape(t, h, dim)

    q, kk, v = stream("q"), stream("k"), stream("v")
    if fault != "no_qk_norm":
        q, kk = _l2(q), _l2(kk)
    q = q / math.sqrt(dim)
    a_log, dt_bias = kda_start(c)
    raw = _mm(_mm(u, p[pre + "f1_weight"], rnd), p[pre + "f2_weight"], rnd)
    g = -jnp.exp(a_log + p[pre + "A_log_offset"])[:, None] * jax.nn.softplus(
        (raw + dt_bias + p[pre + "dt_bias_offset"]).reshape(t, h, dim))
    if fault == "decay_per_head":
        g = jnp.broadcast_to(jnp.mean(g, axis=-1, keepdims=True), g.shape)
    alpha = jnp.exp(g)
    if fault == "bf16_decay":   # named roundings: a cast there and back is
        # excess precision the compiler may drop
        alpha = lax.reduce_precision(jnp.exp(lax.reduce_precision(g, 8, 7)),
                                     8, 7)
    beta = jax.nn.sigmoid(_mm(u, p[pre + "wb_weight"], rnd))
    if fault == "beta_one":
        beta = jnp.ones_like(beta)
    if rnd is not None:
        q, kk, v = rnd(q), rnd(kk), rnd(v)
    o = delta_rule(q, kk, v, alpha, beta, delta=fault != "no_delta")
    gate = _mm(_mm(u, p[pre + "g1_weight"], rnd), p[pre + "g2_weight"], rnd)
    gate = jax.nn.silu(gate) if fault == "gate_silu" \
        else jax.nn.sigmoid(gate)
    o = rms(o, p[pre + "norm_gamma"], c["rms_norm_eps"]).reshape(t, h * dim)
    return _mm(o * gate, p[pre + "wo_weight"], rnd)


def attention(q, k, v, scale, block=256):
    """``q``, ``k`` (T, H, D), ``v`` (T, H, Dv): causal softmax attention,
    a block of queries at a time, each made again for the gradient."""
    t, h, d = q.shape
    j = jnp.arange(t)

    @jax.checkpoint
    def rows(qb, i):
        s = jnp.einsum("qhd,khd->hqk", qb, k) * scale
        p = jax.nn.softmax(jnp.where(i[:, None] >= j[None], s, -jnp.inf),
                           axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v)

    blk = block if t % block == 0 else t
    out = lax.map(lambda a: rows(*a), (q.reshape(t // blk, blk, h, d),
                                       j.reshape(t // blk, blk)))
    return out.reshape(t, -1)


def mla(u, p, pre, c, rnd, fault):
    heads, rank = c["num_attention_heads"], c["kv_lora_rank"]
    nope, rope, dv = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                      c["v_head_dim"])
    t = u.shape[0]
    q = _mm(u, p[pre + "wq_weight"], rnd).reshape(t, heads, nope + rope)
    kva = _mm(u, p[pre + "wkva_weight"], rnd)
    latent, k_pe = kva[:, :rank], kva[:, rank:]
    if fault != "no_kva_norm":
        latent = rms(latent, p[pre + "kva_norm_gamma"], c["rms_norm_eps"])
    kvb = _mm(latent, p[pre + "wkvb_weight"], rnd).reshape(t, heads,
                                                           nope + dv)
    k = jnp.concatenate([kvb[..., :nope], jnp.broadcast_to(
        k_pe[:, None, :], (t, heads, rope))], axis=-1)
    if fault == "no_k_pe":
        q, k = q[..., :nope], k[..., :nope]
    scale = 1.0 / math.sqrt(nope if fault == "mla_scale" else nope + rope)
    if rnd is not None:
        q, k = rnd(q), rnd(k)
    o = attention(q, k, kvb[..., nope:] if rnd is None
                  else rnd(kvb[..., nope:]), scale)
    return _mm(o, p[pre + "wo_weight"], rnd)


def swiglu(m, p, name, rnd):
    return _mm(jax.nn.silu(_mm(m, p[name + "_w1_weight"], rnd))
               * _mm(m, p[name + "_w3_weight"], rnd),
               p[name + "_w2_weight"], rnd)


def routed(m, p, bias, name, c, rnd):
    """``(sum over the held experts, counts of all experts)``."""
    n, k = c["num_experts"], c["num_experts_per_tok"]
    first, count = c.get("experts_held") or (0, n)
    s = jax.nn.sigmoid(_mm(m, p[name + "_router_weight"], rnd))
    _, sel = lax.top_k(lax.stop_gradient(s + bias), k)
    w = jnp.take_along_axis(s, sel, axis=-1)
    if c["route_norm"]:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    w = w * c["route_scale"]
    onehot = jax.nn.one_hot(sel, n, dtype=F32)              # (T, k, n)
    dense = jnp.einsum("tk,tkn->tn", w, onehot)             # (T, n)
    counts = jnp.sum(onehot, axis=(0, 1))

    def mul(a, b):
        return a @ b if rnd is None else rnd(a) @ rnd(b)

    @jax.checkpoint
    def one(acc, args):
        u1, u3, u2, we = args             # (d, f), (d, f), (f, d), (T,)
        return acc + we[:, None] * mul(
            jax.nn.silu(mul(m, u1)) * mul(m, u3), u2), None

    held = dense[:, first:first + count].T                  # (count, T)
    out, _ = lax.scan(one, jnp.zeros_like(m),
                      (p[name + "_expert_w1_weight"],
                       p[name + "_expert_w3_weight"],
                       p[name + "_expert_w2_weight"], held))
    return out, counts


def layer(h, p, aux, i, c, rnd, fault):
    pre = "l%d_" % i
    u = rms(h, p[pre + "attn_norm_gamma"], c["rms_norm_eps"])
    mixer = kda if c["layer_types"][i] == "kda" else mla
    h = h + mixer(u, p, pre + ("kda_" if mixer is kda else "attn_"), c, rnd,
                  fault)
    m = rms(h, p[pre + "ffn_norm_gamma"], c["rms_norm_eps"])
    if i < c["num_dense_layers"]:
        return h + swiglu(m, p, pre + "ffn", rnd), None
    r, counts = routed(m, p, aux[pre + "moe_bias"], pre + "moe", c, rnd)
    return h + swiglu(m, p, pre + "shared", rnd) + r, counts


def head_loss(h, w, labels, rnd, block=2048):
    """Cross-entropy of every position, logits a block at a time."""
    @jax.checkpoint
    def part(hb, yb):
        logits = _mm(hb, w, rnd)
        return jax.nn.logsumexp(logits, axis=-1) \
            - jnp.take_along_axis(logits, yb[:, None], axis=-1)[:, 0]

    t = h.shape[0]
    blk = block if t % block == 0 else t
    return lax.map(lambda a: part(*a), (h.reshape(t // blk, blk, -1),
                                        labels.reshape(t // blk, blk)))


def loss_fn(p, aux, ids, labels, config, operand_round=None, fault=None):
    """``(mean loss, {bias name: counts})`` over sequences ``ids`` (B, T)."""
    c, rnd = config, operand_round

    def sequence(seq, lab):
        h = p["embed_weight"][seq]
        counts = {}
        for i in range(c["num_hidden_layers"]):
            h, n = jax.checkpoint(
                functools.partial(layer, i=i, c=c, rnd=rnd, fault=fault)
            )(h, p, aux)
            if n is not None:
                counts["l%d_moe_bias" % i] = n
        h = rms(h, p["final_norm_gamma"], c["rms_norm_eps"])
        return head_loss(h, p["loss_weight"], lab, rnd), counts

    losses, counts = [], {}
    for seq, lab in zip(ids, labels):       # B is small: one a sequence
        l, n = sequence(seq, lab)
        losses.append(l.reshape(-1))
        for k, v in n.items():
            counts[k] = counts.get(k, 0.0) + v
    return jnp.mean(jnp.concatenate(losses)), counts


def bias_update(bias, counts, coeff):
    delta = coeff * jnp.sign(jnp.mean(counts) - counts)
    return bias + delta - jnp.mean(delta)


@functools.partial(jax.jit, donate_argnums=(0, 1, 2),
                   static_argnames=("state_dtype",))
def adam_leaf(w, m, v, g, lr_t, beta1, beta2, epsilon, wd, state_dtype):
    def held(x):        # the control holds its state in fewer bits
        return x.astype(state_dtype).astype(F32)

    g = g + wd * w
    m = held(beta1 * m + (1 - beta1) * g)
    v = held(beta2 * v + (1 - beta2) * g * g)
    return held(w - lr_t * m / (jnp.sqrt(v) + epsilon)), m, v


@jax.jit
def _norm(x):
    return jnp.sqrt(jnp.sum(jnp.square(x.astype(F32))))


@jax.jit
def _diff_norm(a, b):
    return jnp.sqrt(jnp.sum(jnp.square(a.astype(F32) - b.astype(F32))))


def run_steps(params, aux, batches, lr, momentum, wd, config, beta1, beta2,
              epsilon, small=None, operand_round=None, state_dtype=F32,
              fault=None):
    """Train over ``batches`` ((ids (B, T), next ids (B, T)) each) from
    ``params``/``aux`` and return the readings ``harness/correct.compare``
    reads: each step's loss, the norm of the first gradient (of the mean
    loss) per leaf, the norm of every leaf's change over the steps, and of
    every selection bias's. ``momentum`` is SGD's and unused. ``small``
    holds the sizes of the CPU rehearsal, which replace ``config``'s where
    the parameters handed in are of the rehearsal's hidden size (the
    harness hands every run the same keywords)."""
    if small and params["embed_weight"].shape[1] == small["hidden_size"]:
        config = dict(config, **small)
    state_dtype = jnp.dtype(state_dtype)
    biases = [k for k in aux if k.endswith("_moe_bias")]
    with jax.default_matmul_precision("highest"):
        grad = jax.jit(jax.value_and_grad(
            functools.partial(loss_fn, config=config,
                              operand_round=operand_round, fault=fault),
            has_aux=True))
        p = {k: jnp.asarray(v, F32) for k, v in params.items()}
        b = {k: jnp.asarray(aux[k], F32) for k in biases}
        # the moments rest on the host between steps: the gradient's
        # program then has the device's memory but for the parameters
        m = {k: np.zeros(v.shape, np.float32) for k, v in p.items()}
        v2 = {k: np.zeros(v.shape, np.float32) for k, v in p.items()}
        losses, grad_norms = [], None
        for t, (ids, labels) in enumerate(batches, 1):
            (loss, counts), g = grad(p, b, jnp.asarray(ids, jnp.int32),
                                     jnp.asarray(labels, jnp.int32))
            losses.append(float(loss))
            if grad_norms is None:
                grad_norms = {k: float(_norm(x)) for k, x in g.items()}
            lr_t = lr * math.sqrt(1 - beta2 ** t) / (1 - beta1 ** t)
            for k in list(g):
                p[k], mk, vk = adam_leaf(
                    p[k], jnp.asarray(m[k]), jnp.asarray(v2[k]), g.pop(k),
                    lr_t, beta1, beta2, epsilon, wd, state_dtype=state_dtype)
                m[k], v2[k] = np.asarray(mk), np.asarray(vk)
            b = {k: bias_update(b[k], counts[k],
                                config["load_balance_coeff"]) for k in b}
        change = {k: float(_diff_norm(p[k], jnp.asarray(params[k])))
                  for k in p}
        aux_change = {k: float(_diff_norm(b[k], jnp.asarray(aux[k])))
                      for k in b}
    return dict(losses=losses, grad_norms=grad_norms, change_norms=change,
                aux_change_norms=aux_change)


def layer_forward(h, p, aux, i, config):
    """One layer's output and counts, for the tests of the share."""
    with jax.default_matmul_precision("highest"):
        return layer(h, p, aux, i, config, None, None)
