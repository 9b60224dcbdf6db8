"""Plain reference for the ``lfm2_moe`` hybrid decoder (Liquid AI's
LFM2-8B-A1B): forward pass, next-token cross-entropy, ``jax.grad``,
MXNet's Adam and the router's bias update, in float32 ``jax.numpy`` with
matmul precision ``highest``. No kernel, no sort: the short convolution
is its three-term sum, attention a masked softmax a block of queries at a
time, the routed experts a dense sum over the held experts weighted by a
one-hot of the top-k.

The equations (``RMS(x) = x / sqrt(mean(x^2) + eps) * scale``; (A) marks
what the model's ``config.json`` does not say). ``h = E[ids]``; every
layer is ``h <- h + mixer(RMS(h))``, ``h <- h + ffn(RMS(h))``; the mixers:

- ``conv``: ``[B | C | x] = u W_in`` (d to 3 d, split in that order); ``z
  = B * x``; ``s_t = sum_{j < L} w[:, j] z_{t - (L - 1) + j}``, positions
  before the first nought, no bias, no activation; out ``(C * s) W_out``.
  The taps come as offsets from a start by index (``conv_start``).
- ``full_attention``: ``q = u W_q`` (H heads of D), ``k = u W_k``, ``v = u
  W_v`` (H_kv heads of D; query head h reads K/V head h // (H / H_kv));
  ``q <- RMS_head(q; g_q)``, ``k <- RMS_head(k; g_k)``, one scale of D
  each, then both rotated (the half-split form: the pairs (x[i], x[i +
  D/2]) at frequency ``rope_theta ** (-2i / D)``, position = index);
  position i sees j <= i; scores ``q.k / sqrt(D)``, softmax; out ``o
  W_o``.
- feed-forward: the first ``num_dense_layers`` layers ``(silu(m U1) * (m
  U3)) U2``; the others ``sum_{e in sel} w_e expert_e(m)`` with ``s =
  sigmoid(m Wr)``, ``sel = top_k(s + b)`` (the selection bias ``b`` has no
  gradient), ``w = s[sel] / (sum s[sel] + 1e-20) * route_scale`` (A: the
  published code adds 1e-6), every expert a SwiGLU, no shared expert. Of
  the sum only the terms of ``experts_held = (first, count)`` are
  computed: the share of one chip.
- ``loss = mean over positions of CE(RMS(h) E^T, next id)``: the head is
  the embedding (tied).
- once a training step (A: afmoe's rule, no auxiliary loss): ``c_e`` =
  positions whose ``sel`` holds e; ``delta = load_balance_coeff *
  sign(mean(c) - c)``; ``b <- b + delta - mean(delta)``.
- Adam as MXNet's: ``g = rescale_grad * grad + wd * w``; ``m = b1 m + (1 -
  b1) g``; ``v = b2 v + (1 - b2) g^2``; ``w -= lr sqrt(1 - b2^t) / (1 -
  b1^t) * m / (sqrt(v) + eps)``; masters and moments float32.

It imports nothing of ``mxnet_tpu`` and takes nothing the program made:
parameters come in as a dict by the symbol's documented names
(``embed_weight``, ``l0_conv_in_weight`` (out, in),
``l0_shortconv_weight_offset`` (d, L), ``l2_attn_qnorm_gamma`` (D,),
``l2_moe_expert_w1_weight`` (held, in, width), ``l2_moe_bias``, ...),
made by the benchmark from the seed, and may be host arrays.

``operand_round`` and ``state_dtype`` are for the control only: the
operands of every product and on the way back their gradients rounded to
8-bit floats, and the masters and moments held in bf16: each the step
below what the configuration states. ``fault`` plants one wrong mechanism
(``FAULTS``) for the proof of the limits.
"""
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

F32 = jnp.float32
#: ``bc_swapped``: ``B`` and ``C`` swapped; ``taps_reversed``: the
#: convolution's taps in the other order; ``conv_ahead``: the window one
#: position later, so a position reads the next one's ``B * x`` (not
#: causal); ``no_qk_norm``: the norms of q and k dropped; ``biased_weights``:
#: the experts weighted by the biased scores; ``untied_head``: the head a
#: second matrix drawn from a fixed key with the embedding's spread, not
#: trained
FAULTS = ("bc_swapped", "taps_reversed", "conv_ahead", "no_qk_norm",
          "biased_weights", "untied_head")


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


def conv_start(channels, k):
    """(channels, k) over ``+-1 / sqrt(k)`` (PyTorch's ``Conv1d`` start for
    one channel a group, uniform), spread by index: entry i of the
    flattened weight is ``((487 i mod 1021) + 0.5) / 1021`` of the way, in
    the symbol's arithmetic."""
    bound = 1.0 / math.sqrt(k)
    at = (jnp.arange(channels * k, dtype=F32) * 487.0) % 1021.0
    return (at * (2 * bound / 1021.0) + (bound / 1021.0 - bound)
            ).reshape(channels, k)


def short_conv(u, p, pre, c, rnd, fault):
    """The gated short convolution over ``u`` (T, d)."""
    t, d = u.shape
    k = c["conv_L_cache"]
    bcx = _mm(u, p[pre + "conv_in_weight"], rnd)
    b, cc, x = bcx[:, :d], bcx[:, d:2 * d], bcx[:, 2 * d:]
    if fault == "bc_swapped":
        b, cc = cc, b
    w = conv_start(d, k) + p[pre + "shortconv_weight_offset"]
    if fault == "taps_reversed":
        w = w[:, ::-1]
    ahead = 1 if fault == "conv_ahead" else 0
    z = jnp.pad(b * x, ((k - 1, ahead), (0, 0)))
    s = sum(z[j + ahead:j + ahead + t] * w[:, j] for j in range(k))
    return _mm(cc * s, p[pre + "conv_out_weight"], rnd)


def rotate(x, theta):
    """The half-split rotary embedding of ``x`` (T, heads, D) by
    position."""
    t, _, d = x.shape
    half = d // 2
    inv = theta ** (-jnp.arange(half, dtype=F32) * 2.0 / d)
    ang = jnp.arange(t, dtype=F32)[:, None] * inv[None]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(q, k, v, block=256):
    """``q`` (T, H, D), ``k``, ``v`` (T, H, D) with the K/V heads already
    repeated: causal softmax attention, a block of queries at a time, each
    made again for the gradient."""
    t, h, d = q.shape
    j = jnp.arange(t)

    @jax.checkpoint
    def rows(qb, i):
        s = jnp.einsum("qhd,khd->hqk", qb, k) / math.sqrt(d)
        p = jax.nn.softmax(jnp.where(i[:, None] >= j[None], s, -jnp.inf),
                           axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v)

    blk = block if t % block == 0 else t
    out = lax.map(lambda a: rows(*a), (q.reshape(t // blk, blk, h, d),
                                       j.reshape(t // blk, blk)))
    return out.reshape(t, -1)


def full_attention(u, p, pre, c, rnd, fault):
    heads, kv, dim = (c["num_attention_heads"], c["num_key_value_heads"],
                      c["head_dim"])
    t, eps = u.shape[0], c["rms_norm_eps"]
    pre = pre + "attn_"
    q = _mm(u, p[pre + "wq_weight"], rnd).reshape(t, heads, dim)
    k = _mm(u, p[pre + "wk_weight"], rnd).reshape(t, kv, dim)
    v = _mm(u, p[pre + "wv_weight"], rnd).reshape(t, kv, dim)
    if fault != "no_qk_norm":
        q = rms(q, p[pre + "qnorm_gamma"], eps)
        k = rms(k, p[pre + "knorm_gamma"], eps)
    q, k = rotate(q, c["rope_theta"]), rotate(k, c["rope_theta"])
    k, v = (jnp.repeat(x, heads // kv, axis=1) for x in (k, v))
    if rnd is not None:
        q, k, v = rnd(q), rnd(k), rnd(v)
    return _mm(attention(q, k, v), p[pre + "wo_weight"], rnd)


def swiglu(m, p, name, rnd):
    return _mm(jax.nn.silu(_mm(m, p[name + "_w1_weight"], rnd))
               * _mm(m, p[name + "_w3_weight"], rnd),
               p[name + "_w2_weight"], rnd)


def routed(m, p, bias, name, c, rnd, fault=None):
    """``(sum over the held experts, counts of all experts)``."""
    n, k = c["num_experts"], c["num_experts_per_tok"]
    first, count = c.get("experts_held") or (0, n)
    s = jax.nn.sigmoid(_mm(m, p[name + "_router_weight"], rnd))
    _, sel = lax.top_k(lax.stop_gradient(s + bias), k)
    w = jnp.take_along_axis(s + bias if fault == "biased_weights" else s,
                            sel, axis=-1)
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


_MIXERS = {"conv": short_conv, "full_attention": full_attention}


def layer(h, p, aux, i, c, rnd, fault):
    pre = "l%d_" % i
    u = rms(h, p[pre + "attn_norm_gamma"], c["rms_norm_eps"])
    h = h + _MIXERS[c["layer_types"][i]](u, p, pre, c, rnd, fault)
    m = rms(h, p[pre + "ffn_norm_gamma"], c["rms_norm_eps"])
    if i < c["num_dense_layers"]:
        return h + swiglu(m, p, pre + "ffn", rnd), None
    r, counts = routed(m, p, aux[pre + "moe_bias"], pre + "moe", c, rnd,
                       fault)
    return h + r, counts


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


def head_matrix(p, fault=None):
    """The head: the embedding itself, or with ``untied_head`` a second
    matrix of its shape and spread drawn from a fixed key."""
    e = p["embed_weight"]
    if fault != "untied_head":
        return e
    return lax.stop_gradient(jnp.std(e)) * jax.random.normal(
        jax.random.PRNGKey(40), e.shape, F32)


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
        return head_loss(h, head_matrix(p, fault), lab, rnd), counts

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


def layer_forward(h, p, aux, i, config, fault=None):
    """One layer's output and counts, for the tests of the share."""
    with jax.default_matmul_precision("highest"):
        return layer(h, p, aux, i, config, None, fault)
