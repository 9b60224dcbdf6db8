"""Plain reference for the ``nemotron_h`` hybrid decoder (NVIDIA
Nemotron-H / Nemotron 3 family): forward pass, next-token cross-entropy,
``jax.grad``, MXNet's Adam and the router's bias update, in float32
``jax.numpy`` with matmul precision ``highest``. No kernel, no sort, no
chunked form: the state-space recurrence is a ``lax.scan`` over tokens
(taken a block of tokens at a time for the gradient, so that it fits),
attention a masked softmax a block of queries at a time, the routed
experts a dense sum over the held experts weighted by a one-hot of the
top-k.

The equations (``RMS(x) = x / sqrt(mean(x^2) + eps) * scale``; (A) marks
what the model's ``config.json`` does not say and HF's
``modeling_nemotron_h``, as known without a network, does). ``h = E[ids]``;
every block is ``h <- h + mixer(RMS(h))``; the kinds:

- ``mamba`` (H heads of P, state N, G groups, conv width K): ``[z | xBC |
  dt] = u W_in`` (widths HP | HP + 2GN | H); ``xBC <- silu(conv(xBC) + b)``,
  causal and depthwise, ``conv(x)[t] = sum_k w[:, k] x[t - (K - 1) + k]``;
  ``x`` (T, H, P), ``B``, ``C`` (T, G, N) its parts (head h reads group ``h
  // (H / G)``); ``delta = softplus(dt + dt_bias)``; ``A = -exp(A_log)``;
  ``S_t = exp(delta_t A) S_{t-1} + delta_t x_t B_t^T``; ``y_t = S_t C_t + D
  x_t``; ``y <- RMS(y * silu(z))`` over each group's HP / G features (A: the
  gate before the norm), one scale a feature; out ``y W_out``. ``A_log`` and
  ``dt_bias`` come as offsets from Mamba-2's starting values by head index
  (``mamba_start``), the convolution's weight as an offset from its start by
  (channel, tap) index (``conv_start``), ``D`` as itself.
- ``moe``: ``s = sigmoid(m Wr)``; ``sel = top_k(s + b)`` with the selection
  bias ``b`` (no gradient); ``w = s[sel] / (sum s[sel] + 1e-20) *
  route_scale``; ``shared(m) + sum_{e in sel} w_e expert_e(m)``, every
  expert and the shared one ``relu(m U1)^2 U2``. Of the sum only the terms of
  ``experts_held = (first, count)`` are computed: the share of one chip.
- ``attention``: ``q = a Wq``, ``k = a Wk``, ``v = a Wv``; ``heads /
  kv_heads`` query heads share a K/V head; position i sees j <= i; scores
  ``q.k / sqrt(head_dim)``, softmax; no rotation (A: the modelling code
  applies none), no norm, no gate; out ``o Wo``.
- ``loss = mean over positions of CE(RMS(h) Wout, next id)``.
- once a training step (A: DeepSeek-V3's rule): ``c_e`` = positions whose
  ``sel`` holds e; ``delta = load_balance_coeff * sign(mean(c) - c)``; ``b
  <- b + delta - mean(delta)``.
- Adam as MXNet's: ``g = rescale_grad * grad + wd * w``; ``m = b1 m + (1 -
  b1) g``; ``v = b2 v + (1 - b2) g^2``; ``w -= lr sqrt(1 - b2^t) / (1 -
  b1^t) * m / (sqrt(v) + eps)``; masters and moments float32.

It imports nothing of ``mxnet_tpu`` and takes nothing the program made:
parameters come in as a dict by the symbol's documented names
(``embed_weight``, ``l0_mixer_in_weight`` (out, in), ``l0_mixer_conv_weight_offset``
(channels, K), ``l1_moe_expert_w1_weight`` (held, in, width), ``l1_moe_bias``,
...), made by the benchmark from the seed, and may be host arrays.

``operand_round`` and ``state_dtype`` are for the control only: the
operands of every product (the recurrence's ``delta x``, ``B`` and ``C``
among them) and on the way back their gradients rounded to 8-bit floats,
and the masters and moments held in bf16: each the step below what the
configuration states. ``fault`` plants one wrong mechanism (``FAULTS``)
for the proof of the limits.
"""
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

F32 = jnp.float32
#: ``no_gate``: ``silu(z)`` dropped before the mixer's norm; ``decay_sign``:
#: ``A = +exp(A_log)``; ``no_skip``: ``D x`` dropped; ``relu_not_squared``:
#: the experts' ``relu(.)^2`` as ``relu(.)``; ``bf16_decay``: ``delta A`` and
#: its exponential rounded to bfloat16
FAULTS = ("no_gate", "decay_sign", "no_skip", "relu_not_squared",
          "bf16_decay")


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


def relu2(x, fault=None):
    r = jax.nn.relu(x)
    return r if fault == "relu_not_squared" else r * r


def mamba_start(c):
    """``(A_log, dt_bias)`` (H,) at their start: ``A`` spread evenly over
    ``a_init_range``, ``delta`` log-spaced over ``time_step_min ..
    time_step_max``, by head index; ``dt_bias`` is ``delta``'s inverse
    softplus."""
    h = c["mamba_num_heads"]
    at = jnp.arange(h, dtype=F32) / max(h - 1, 1)
    lo, hi = c.get("a_init_range", (1.0, 16.0))
    delta = jnp.exp(math.log(c["time_step_min"])
                    + at * (math.log(c["time_step_max"])
                            - math.log(c["time_step_min"])))
    return jnp.log(lo + at * (hi - lo)), delta + jnp.log(-jnp.expm1(-delta))


def conv_start(channels, k):
    """(channels, k) over ``+-1 / sqrt(k)`` (PyTorch's ``Conv1d`` start,
    uniform), spread by index: entry i of the flattened weight is ``((487 i
    mod 1021) + 0.5) / 1021`` of the way."""
    at = (jnp.arange(channels * k, dtype=F32) * 487.0) % 1021.0
    bound = 1.0 / math.sqrt(k)
    return (at * (2 * bound / 1021.0) + (bound / 1021.0 - bound)
            ).reshape(channels, k)


def recurrence(xd, decay, b, c, block=128):
    """``S_t = decay_t S_{t-1} + xd_t b_t^T``, ``y_t = S_t c_t``, from ``S =
    0``: ``xd`` (T, G, R, P), ``decay`` (T, G, R), ``b``/``c`` (T, G, N);
    returns ``y`` (T, G, R, P). A scan over tokens; for the gradient a
    block of tokens is made again from the state that entered it."""
    t, g, r, p = xd.shape
    n = b.shape[-1]

    def token(s, a):
        xt, dt, bt, ct = a
        s = dt[..., None, None] * s \
            + xt[..., None] * bt[:, None, None, :]
        return s, jnp.sum(s * ct[:, None, None, :], axis=-1)

    @jax.checkpoint
    def tokens(s, a):
        return lax.scan(token, s, a)

    blk = block if t % block == 0 else t
    _, y = lax.scan(tokens, jnp.zeros((g, r, p, n), F32), tuple(
        v.reshape((t // blk, blk) + v.shape[1:])
        for v in (xd, decay, b, c)))
    return y.reshape(t, g, r, p)


def mamba(u, p, pre, c, rnd, fault):
    h, dim = c["mamba_num_heads"], c["mamba_head_dim"]
    n, g, k = c["ssm_state_size"], c["n_groups"], c["conv_kernel"]
    inner, bc, t = h * dim, g * n, u.shape[0]
    proj = _mm(u, p[pre + "in_weight"], rnd)
    z, xbc, dt = (proj[:, :inner], proj[:, inner:2 * inner + 2 * bc],
                  proj[:, 2 * inner + 2 * bc:])
    w = conv_start(inner + 2 * bc, k) + p[pre + "conv_weight_offset"]
    padded = jnp.pad(xbc, ((k - 1, 0), (0, 0)))
    xbc = jax.nn.silu(p[pre + "conv_bias"]
                      + sum(padded[i:i + t] * w[:, i] for i in range(k)))
    x = xbc[:, :inner].reshape(t, g, h // g, dim)
    b = xbc[:, inner:inner + bc].reshape(t, g, n)
    cc = xbc[:, inner + bc:].reshape(t, g, n)
    a_log, dt_bias = mamba_start(c)
    delta = jax.nn.softplus(dt + dt_bias + p[pre + "dt_bias_offset"])
    a = jnp.exp(a_log + p[pre + "A_log_offset"])
    a = delta * (a if fault == "decay_sign" else -a)
    decay = jnp.exp(a)
    if fault == "bf16_decay":   # named roundings: a cast there and back is
        # excess precision the compiler may drop
        decay = lax.reduce_precision(jnp.exp(lax.reduce_precision(a, 8, 7)),
                                     8, 7)
    xd = x * delta.reshape(t, g, h // g, 1)
    if rnd is not None:
        xd, b, cc = rnd(xd), rnd(b), rnd(cc)
    y = recurrence(xd, decay.reshape(t, g, h // g), b, cc)
    if fault != "no_skip":
        y = y + p[pre + "D_gamma"].reshape(g, h // g, 1) * x
    y = y.reshape(t, g, inner // g)
    if fault != "no_gate":
        y = y * jax.nn.silu(z).reshape(y.shape)
    y = rms(y, p[pre + "norm_gamma"].reshape(g, -1), c["rms_norm_eps"])
    return _mm(y.reshape(t, inner), p[pre + "out_weight"], rnd)


def attention(q, k, v, block=256):
    """``q`` (T, Hq, D), ``k``/``v`` (T, Hkv, D): causal softmax attention,
    a block of queries at a time, each made again for the gradient."""
    t, hq, d = q.shape
    hkv = k.shape[1]
    qg = q.reshape(t, hkv, hq // hkv, d)
    j = jnp.arange(t)

    @jax.checkpoint
    def rows(qb, i):
        s = jnp.einsum("qhgd,khd->hgqk", qb, k) / math.sqrt(d)
        p = jax.nn.softmax(jnp.where(i[:, None] >= j[None], s, -jnp.inf),
                           axis=-1)
        return jnp.einsum("hgqk,khd->qhgd", p, v)

    blk = block if t % block == 0 else t
    out = lax.map(lambda a: rows(*a), (qg.reshape(t // blk, blk, hkv, -1, d),
                                       j.reshape(t // blk, blk)))
    return out.reshape(t, hq * d)


def attention_block(a, p, pre, c, rnd):
    heads, kv_heads, dim = (c["num_attention_heads"],
                            c["num_key_value_heads"], c["head_dim"])
    t = a.shape[0]
    q = _mm(a, p[pre + "wq_weight"], rnd).reshape(t, heads, dim)
    k = _mm(a, p[pre + "wk_weight"], rnd).reshape(t, kv_heads, dim)
    v = _mm(a, p[pre + "wv_weight"], rnd).reshape(t, kv_heads, dim)
    return _mm(attention(q, k, v), p[pre + "wo_weight"], rnd)


def routed(m, p, bias, name, c, rnd, fault=None):
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
        u1, u2, we = args                     # (d, f), (f, d), (T,)
        return acc + we[:, None] * mul(relu2(mul(m, u1), fault), u2), None

    held = dense[:, first:first + count].T                  # (count, T)
    out, _ = lax.scan(one, jnp.zeros_like(m),
                      (p[name + "_expert_w1_weight"],
                       p[name + "_expert_w2_weight"], held))
    return out, counts


def experts_block(m, p, bias, pre, c, rnd, fault):
    shared = _mm(relu2(_mm(m, p[pre + "shared_w1_weight"], rnd), fault),
                 p[pre + "shared_w2_weight"], rnd)
    r, counts = routed(m, p, bias, pre + "moe", c, rnd, fault)
    return shared + r, counts


def block(h, p, aux, i, c, rnd, fault):
    pre = "l%d_" % i
    u = rms(h, p[pre + "norm_gamma"], c["rms_norm_eps"])
    kind, counts = c["layer_types"][i], None
    if kind == "mamba":
        out = mamba(u, p, pre + "mixer_", c, rnd, fault)
    elif kind == "moe":
        out, counts = experts_block(u, p, aux[pre + "moe_bias"], pre, c, rnd,
                                    fault)
    else:
        out = attention_block(u, p, pre + "attn_", c, rnd)
    return h + out, counts


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
                functools.partial(block, i=i, c=c, rnd=rnd, fault=fault)
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
        # program then has the device's memory but for the parameters (the
        # control's roundings need 7.3 GB of temporaries at the cell's size)
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


def moe_layer(m, p, bias, name, config):
    """One layer's routed sum and counts, for the tests of the share."""
    with jax.default_matmul_precision("highest"):
        return routed(m, p, bias, name, config, None)
