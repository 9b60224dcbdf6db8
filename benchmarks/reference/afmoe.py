"""Plain reference for the ``afmoe`` decoder (arcee-ai Trinity family):
forward pass, next-token cross-entropy, ``jax.grad``, MXNet's Adam and the
router's bias update, in float32 ``jax.numpy`` with matmul precision
``highest``. No kernel, no sort, no cache: attention is a masked softmax
(taken a block of queries at a time so that it fits), the routed experts
are a dense sum over the held experts weighted by a one-hot of the top-k.

The equations (``RMS(x) = x / sqrt(mean(x^2) + eps) * scale``; (A) marks
what the model's ``config.json`` does not say and the family's public
modelling code, as known without a network, does):

- ``h = E[ids] * sqrt(d)`` (A: ``mup_enabled``).
- every layer: ``a = RMS(h)``; ``q = a Wq``, ``k = a Wk``, ``v = a Wv``,
  ``g = a Wg`` (A: an output gate), no bias; ``q, k <- RMS(q), RMS(k)`` per
  head over ``head_dim`` (A: QK-norm); on ``sliding_attention`` layers
  ``q, k <- RoPE(q, k)`` (half-split pairs, ``theta`` 10000, no scaling)
  and position i sees j with 0 <= i - j < ``sliding_window`` (A: the
  window counts the position itself); on ``full_attention`` layers no
  rotation (A) and i sees j <= i; scores ``q.k / sqrt(head_dim)``, softmax;
  ``heads / kv_heads`` query heads share a K/V head; ``o = (P v) *
  sigmoid(g)``; ``h <- h + RMS(o Wo)`` (A: a norm before and after each
  sub-block, the residual added after the second).
- the first ``num_dense_layers`` layers: ``m = RMS(h)``; ``h <- h +
  RMS((silu(m W1) * (m W3)) W2)``.
- the other layers: ``m = RMS(h)``; ``s = sigmoid(m Wr)``; ``sel =
  top_k(s + b)`` with the selection bias ``b`` (no gradient); ``w = s[sel]
  / (sum s[sel] + 1e-20) * route_scale``; ``h <- h + RMS(shared(m) +
  sum_{e in sel} w_e expert_e(m))``, every expert ``(silu(m U1) * (m U3))
  U2``. Of the sum only the terms of ``experts_held = (first, count)`` are
  computed: the share of one chip of the deployment.
- ``loss = mean over positions of CE(RMS(h) Wout, next id)``.
- once a training step (A: torchtitan's rule): ``c_e`` = positions whose
  ``sel`` holds e; ``delta = load_balance_coeff * sign(mean(c) - c)``;
  ``b <- b + delta - mean(delta)``.
- Adam as MXNet's: ``g = rescale_grad * grad + wd * w``; ``m = b1 m +
  (1 - b1) g``; ``v = b2 v + (1 - b2) g^2``; ``w -= lr sqrt(1 - b2^t) /
  (1 - b1^t) * m / (sqrt(v) + eps)``; masters and moments float32.

It imports nothing of ``mxnet_tpu`` and takes nothing the program made:
parameters come in as a dict by the symbol's documented names
(``embed_weight``, ``l3_attn_wq_weight`` (out, in), ``l3_moe_expert_w1_weight``
(held, in, width), ``l3_moe_bias``, ...), made by the benchmark from the
seed, and may be host arrays: they are put on the device leaf by leaf.

``config["router_round"]`` (a type's name) rounds the router's operands
alone to that type: with ``bfloat16`` the top-k flips where the program's
bf16 router flips it and nothing else differs, which measures how much of
the program's distance from this reference the flips explain (PERF.md).

``operand_round`` and ``state_dtype`` are for the control only: the
operands of every matrix product (and on the way back their gradients)
rounded to 8-bit floats, and the masters and moments held in bf16: each
the step below what the configuration states. ``fault`` plants one wrong
mechanism (``FAULTS``) for the proof of the limits.
"""
import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

F32 = jnp.float32
FAULTS = ("no_routed_experts", "no_window", "rope_on_full")


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


def rope(x, theta):
    """``x`` (T, heads, D): pairs (x[i], x[i + D/2]) turned by ``pos *
    theta^(-2i/D)``."""
    t, _, d = x.shape
    half = d // 2
    inv = theta ** (-jnp.arange(half, dtype=F32) * 2.0 / d)
    ang = jnp.arange(t, dtype=F32)[:, None, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(q, k, v, window, block=256):
    """``q`` (T, Hq, D), ``k``/``v`` (T, Hkv, D): causal softmax attention,
    with ``window`` only over 0 <= i - j < window. A block of queries at a
    time, each made again for the gradient."""
    t, hq, d = q.shape
    hkv = k.shape[1]
    qg = q.reshape(t, hkv, hq // hkv, d)
    j = jnp.arange(t)

    @jax.checkpoint
    def rows(qb, i):
        s = jnp.einsum("qhgd,khd->hgqk", qb, k) / math.sqrt(d)
        mask = i[:, None] >= j[None]
        if window:
            mask = mask & (i[:, None] - j[None] < window)
        p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
        return jnp.einsum("hgqk,khd->qhgd", p, v)

    blk = block if t % block == 0 else t
    out = lax.map(lambda a: rows(*a), (qg.reshape(t // blk, blk, hkv, -1, d),
                                       j.reshape(t // blk, blk)))
    return out.reshape(t, hq * d)


def gated(m, p, name, rnd):
    return _mm(jax.nn.silu(_mm(m, p[name + "_w1_weight"], rnd))
               * _mm(m, p[name + "_w3_weight"], rnd),
               p[name + "_w2_weight"], rnd)


def routed(m, p, bias, name, c, rnd):
    """``(sum over the held experts, counts of all experts)``."""
    n, k = c["num_experts"], c["num_experts_per_tok"]
    first, count = c.get("experts_held") or (0, n)
    wr = p[name + "_router_weight"]
    if c.get("router_round"):       # a measurement, not the model: below
        s = jax.nn.sigmoid(_mm(m.astype(c["router_round"]).astype(F32),
                               wr.astype(c["router_round"]).astype(F32),
                               rnd))
    else:
        s = jax.nn.sigmoid(_mm(m, wr, rnd))
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
        u1, u3, u2, we = args                 # (d, f), (d, f), (f, d), (T,)
        y = mul(jax.nn.silu(mul(m, u1)) * mul(m, u3), u2)
        return acc + we[:, None] * y, None

    held = dense[:, first:first + count].T                  # (count, T)
    out, _ = lax.scan(one, jnp.zeros_like(m),
                      (p[name + "_expert_w1_weight"], p[name + "_expert_w3_weight"],
                       p[name + "_expert_w2_weight"], held))
    return out, counts


def layer(h, p, aux, i, c, rnd, fault):
    pre = "l%d_" % i
    eps, dim = c["rms_norm_eps"], c["head_dim"]
    heads, kv_heads = c["num_attention_heads"], c["num_key_value_heads"]
    sliding = c["layer_types"][i] == "sliding_attention"
    t = h.shape[0]

    a = rms(h, p[pre + "attn_norm_gamma"], eps)
    q = _mm(a, p[pre + "attn_wq_weight"], rnd).reshape(t, heads, dim)
    k = _mm(a, p[pre + "attn_wk_weight"], rnd).reshape(t, kv_heads, dim)
    v = _mm(a, p[pre + "attn_wv_weight"], rnd).reshape(t, kv_heads, dim)
    g = _mm(a, p[pre + "attn_wg_weight"], rnd)
    q = rms(q, p[pre + "attn_qnorm_gamma"], eps)
    k = rms(k, p[pre + "attn_knorm_gamma"], eps)
    if sliding or fault == "rope_on_full":
        q, k = rope(q, c["rope_theta"]), rope(k, c["rope_theta"])
    window = c["sliding_window"] if sliding and fault != "no_window" else 0
    o = attention(q, k, v, window) * jax.nn.sigmoid(g)
    h = h + rms(_mm(o, p[pre + "attn_wo_weight"], rnd),
                p[pre + "attn_postnorm_gamma"], eps)

    m = rms(h, p[pre + "ffn_norm_gamma"], eps)
    counts = None
    if i < c["num_dense_layers"]:
        f = gated(m, p, pre + "ffn", rnd)
    else:
        f = gated(m, p, pre + "shared", rnd)
        r, counts = routed(m, p, aux[pre + "moe_bias"], pre + "moe", c, rnd)
        if fault != "no_routed_experts":
            f = f + r
    return h + rms(f, p[pre + "ffn_postnorm_gamma"], eps), counts


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
    d = c["hidden_size"]

    def sequence(seq, lab):
        h = p["embed_weight"][seq]
        if c.get("mup_enabled"):
            h = h * math.sqrt(d)
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
    losses = jnp.concatenate(losses) if losses else jnp.zeros((0,), F32)
    return jnp.mean(losses), counts


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
        m = {k: jnp.zeros_like(v) for k, v in p.items()}
        v2 = {k: jnp.zeros_like(v) for k, v in p.items()}
        losses, grad_norms = [], None
        for t, (ids, labels) in enumerate(batches, 1):
            (loss, counts), g = grad(p, b, jnp.asarray(ids, jnp.int32),
                                     jnp.asarray(labels, jnp.int32))
            losses.append(float(loss))
            if grad_norms is None:
                grad_norms = {k: float(_norm(x)) for k, x in g.items()}
            lr_t = lr * math.sqrt(1 - beta2 ** t) / (1 - beta1 ** t)
            for k in list(g):
                p[k], m[k], v2[k] = adam_leaf(
                    p[k], m[k], v2[k], g.pop(k), lr_t, beta1, beta2,
                    epsilon, wd, state_dtype=state_dtype)
            b = {k: bias_update(b[k], counts[k],
                                config["load_balance_coeff"]) for k in b}
        change = {k: float(_diff_norm(p[k], jnp.asarray(params[k])))
                  for k in p}
        aux_change = {k: float(_diff_norm(b[k], jnp.asarray(aux[k])))
                      for k in b}
    return dict(losses=losses, grad_norms=grad_norms, change_norms=change,
                aux_change_norms=aux_change)


def moe_layer(m, p, bias, name, config):
    """One layer's routed sum and counts, for the tests of the share."""
    with jax.default_matmul_precision("highest"):
        return routed(m, p, bias, name, config, None)

