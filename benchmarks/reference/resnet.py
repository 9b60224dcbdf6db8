"""Plain reference for the symbolic ResNet (v1, bottleneck or basic units).

Forward pass, softmax cross-entropy, ``jax.grad`` and MXNet's
multi-precision SGD-momentum update in float32 ``jax.numpy`` with matmul
precision ``highest``. Written from He et al. 2015 (arXiv:1512.03385),
Table 1, and from MXNet 0.12's documented operators:

- ``Convolution`` NCHW, weight OIHW, no bias;
- ``BatchNorm`` (training): per-channel mean and *biased* variance over
  N, H, W; ``y = gamma * (x - mean) / sqrt(var + eps) + beta``;
  ``moving = moving * momentum + batch * (1 - momentum)``;
- ``Pooling`` max 3x3 stride 2 pad 1 ("valid" convention), global average;
- ``FullyConnected`` ``y = x W^T + b``; ``SoftmaxOutput`` whose gradient
  is ``softmax - onehot`` per row (no normalisation);
- ``SGD`` with ``multi_precision``: ``mom = momentum * mom - lr *
  (rescale_grad * grad + wd * w32)``; ``w32 += mom``.

The v1 unit strides in its first 1x1 convolution (as MXNet's example
symbol does), not in the 3x3.

It imports nothing of ``mxnet_tpu`` and takes nothing the program made:
parameters come in as a dict by the symbol's documented names
(``conv0_weight``, ``stage2_unit1_bn1_gamma``, ``fc1_bias``, ...), made
by the benchmark from the seed.

``operand_round`` is for the control only: it rounds what enters every
convolution and the classifier (activations and weights, and on the way
back their gradients) to 8 bits with one scale per tensor, the step below
the bf16 the configurations state. ``state_dtype`` is the control's other
half: the float32 masters and momentum held in bf16 instead. The control
is both together (``CONTROL``, with e5m2 operands). The reference proper
leaves both alone.
"""
import functools

import jax
import jax.numpy as jnp
from jax import lax

HIGHEST = lax.Precision.HIGHEST
F32 = jnp.float32


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


def _conv(x, w, stride, pad, rnd):
    if rnd is not None:
        x, w = rnd(x), rnd(w)
    return lax.conv_general_dilated(
        x, w, (stride, stride), [(pad, pad), (pad, pad)],
        dimension_numbers=("NCHW", "OIHW", "NCHW"), precision=HIGHEST)


def _bn(x, p, aux, name, eps, momentum):
    """Training-mode BatchNorm; returns the output and the new moving
    statistics of this layer."""
    mean = jnp.mean(x, axis=(0, 2, 3))
    var = jnp.mean(jnp.square(x - mean[None, :, None, None]), axis=(0, 2, 3))
    inv = lax.rsqrt(var + eps)
    y = (x - mean[None, :, None, None]) * (inv * p[name + "_gamma"])[
        None, :, None, None] + p[name + "_beta"][None, :, None, None]
    new = {
        name + "_moving_mean": aux[name + "_moving_mean"] * momentum
        + lax.stop_gradient(mean) * (1 - momentum),
        name + "_moving_var": aux[name + "_moving_var"] * momentum
        + lax.stop_gradient(var) * (1 - momentum),
    }
    return y, new


def _unit(x, p, aux, name, stride, dim_match, bottle_neck, eps, momentum,
          rnd):
    new = {}

    def cbr(h, conv, bn, s, pad, relu=True):
        h = _conv(h, p[name + conv + "_weight"], s, pad, rnd)
        h, n = _bn(h, p, aux, name + bn, eps, momentum)
        new.update(n)
        return jax.nn.relu(h) if relu else h

    if bottle_neck:
        body = cbr(x, "_conv1", "_bn1", stride, 0)
        body = cbr(body, "_conv2", "_bn2", 1, 1)
        body = cbr(body, "_conv3", "_bn3", 1, 0, relu=False)
    else:
        body = cbr(x, "_conv1", "_bn1", stride, 1)
        body = cbr(body, "_conv2", "_bn2", 1, 1, relu=False)
    short = x if dim_match else cbr(x, "_sc", "_sc_bn", stride, 0,
                                    relu=False)
    return jax.nn.relu(body + short), new


def forward(p, aux, x, units, bottle_neck, eps=2e-5, bn_momentum=0.9,
            operand_round=None, remat=True):
    """Logits ``(N, classes)`` and the new moving statistics."""
    rnd = operand_round
    new_aux = {}
    h = _conv(x.astype(F32), p["conv0_weight"], 2, 3, rnd)
    h, n = _bn(h, p, aux, "bn0", eps, bn_momentum)
    new_aux.update(n)
    h = jax.nn.relu(h)
    h = lax.reduce_window(h, -jnp.inf, lax.max, (1, 1, 3, 3), (1, 1, 2, 2),
                          [(0, 0), (0, 0), (1, 1), (1, 1)])
    for i, n_units in enumerate(units):
        for j in range(n_units):
            name = "stage%d_unit%d" % (i + 1, j + 1)
            fn = functools.partial(
                _unit, name=name, stride=(1 if i == 0 or j else 2),
                dim_match=j > 0, bottle_neck=bottle_neck, eps=eps,
                momentum=bn_momentum, rnd=rnd)
            if remat:   # keep only each unit's input for the backward pass
                fn = jax.checkpoint(fn)
            h, n = fn(h, p, aux)
            new_aux.update(n)
    h = jnp.mean(h, axis=(2, 3))
    w, b = p["fc1_weight"], p["fc1_bias"]
    if rnd is not None:
        h, w = rnd(h), rnd(w)
    logits = jnp.dot(h, w.T, precision=HIGHEST) + b
    return logits, new_aux


def loss_fn(p, aux, x, labels, **model):
    """Mean softmax cross-entropy over the rows, and the new statistics."""
    logits, new_aux = forward(p, aux, x, **model)
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(logp, labels.astype(jnp.int32)[:, None], 1)
    return -jnp.mean(picked), new_aux


def train_step(state, x, labels, lr, momentum, wd, state_dtype=F32, **model):
    """One step of multi-precision SGD-momentum on float32 masters.

    ``state`` is ``(w32, mom, aux)``, float32 unless the control says
    otherwise (``state_dtype``); the gradient is that of the *mean*
    loss, i.e. the optimizer's ``rescale_grad = 1 / batch`` applied to the
    summed gradient ``SoftmaxOutput`` hands back. Returns the new state,
    the loss and the gradient."""
    w32, mom, aux = state
    (loss, new_aux), grad = jax.value_and_grad(loss_fn, has_aux=True)(
        w32, aux, x, labels, **model)
    def held(v):        # the control holds its state in fewer bits
        return v.astype(state_dtype).astype(F32)

    new_mom = {k: held(momentum * mom[k] - lr * (grad[k] + wd * w32[k]))
               for k in w32}
    new_w = {k: held(w32[k] + new_mom[k]) for k in w32}
    return (new_w, new_mom, new_aux), loss, grad


#: the reference computed one step below what the configurations state
CONTROL = dict(operand_round=fake_fp8, state_dtype="bfloat16")


def leaf_norms(tree):
    """The 2-norm of every leaf, as a dict of scalars."""
    return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(F32))))
            for k, v in tree.items()}


def run_steps(params, aux, batches, lr, momentum, wd, units, bottle_neck,
              eps=2e-5, bn_momentum=0.9, operand_round=None,
              state_dtype=F32):
    """Drive ``len(batches)`` steps from ``params`` (any float dtype; held
    as float32 masters) and return what the benchmark compares:

    ``losses`` per step, ``grad_norms`` of the first step's gradient per
    leaf, ``change_norms`` of the parameters' change over all steps per
    leaf, ``aux_change_norms`` of the moving statistics' change per leaf.
    """
    model = dict(units=tuple(units), bottle_neck=bottle_neck, eps=eps,
                 bn_momentum=bn_momentum, operand_round=operand_round)
    step = jax.jit(functools.partial(train_step, lr=lr, momentum=momentum,
                                     wd=wd, state_dtype=jnp.dtype(state_dtype),
                                     **model))
    w0 = {k: v.astype(F32) for k, v in params.items()}
    a0 = {k: v.astype(F32) for k, v in aux.items()}
    state = (w0, {k: jnp.zeros_like(v) for k, v in w0.items()}, a0)
    losses, grad_norms = [], None
    norms = jax.jit(leaf_norms)
    for i, (x, y) in enumerate(batches):
        state, loss, grad = step(state, x, y)
        losses.append(loss)
        if i == 0:
            grad_norms = norms(grad)
        del grad
    diff = jax.jit(lambda a, b: leaf_norms({k: a[k] - b[k] for k in a}))
    return dict(losses=[float(v) for v in losses],
                grad_norms={k: float(v) for k, v in grad_norms.items()},
                change_norms={k: float(v)
                              for k, v in diff(state[0], w0).items()},
                aux_change_norms={k: float(v)
                                  for k, v in diff(state[2], a0).items()})
