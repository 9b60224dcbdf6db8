"""BatchNorm's batch statistics: one read of half-precision data.

For bfloat16 / float16 data `ops/nn.py` `_bn_stats` takes the batch mean
and variance from one pass in float32 (`sum(d)`, `sum(d*d)`, `d = data -
stop_gradient(moving_mean)`), so both ride with whatever produces `data`
and the derivative is elementwise; float32 data keeps `jnp.mean` and
`jnp.var`. Here: the numbers against a float64 two-pass NumPy reference,
forward and backward, and a count of the reductions autodiff leaves in the
program (a count, not a time: the CPU lane's kind of guard).
"""
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu  # noqa: F401  (registers the operators)
from mxnet_tpu.ops.registry import get_op

EPS = 1e-3
CHANNELS = 4
_bn = get_op("BatchNorm").fn


def _layout(axis):
    shape = (32, CHANNELS, 14, 14) if axis == 1 else (32, 14, 14, CHANNELS)
    axis %= len(shape)
    red = tuple(i for i in range(len(shape)) if i != axis)
    bshape = [CHANNELS if i == axis else 1 for i in range(len(shape))]
    return shape, red, bshape


def _reference(x, gamma, beta, red, bshape, dy, dmean, dvar):
    """Float64, two passes: the textbook forward and backward."""
    n = x.size // CHANNELS
    mean = x.mean(axis=red)
    xc = x - mean.reshape(bshape)
    var = (xc * xc).mean(axis=red)
    inv = 1 / np.sqrt(var + EPS)
    xh = xc * inv.reshape(bshape)
    out = xh * gamma.reshape(bshape) + beta.reshape(bshape)
    dxh = dy * gamma.reshape(bshape)
    dx = inv.reshape(bshape) * (
        dxh - dxh.mean(axis=red).reshape(bshape)
        - xh * (dxh * xh).mean(axis=red).reshape(bshape))
    dx += dmean.reshape(bshape) / n + dvar.reshape(bshape) * 2 * xc / n
    return out, mean, var, dx, (dy * xh).sum(axis=red), dy.sum(axis=red)


def _two_pass(data, gamma, beta, axis):
    """The formulas `batch_norm` had before the statistics became one-pass
    (`jnp.mean`, then `jnp.var`), at the data's own dtype: what bfloat16
    outputs and gradients are held to, since against float64 the bfloat16
    apply (`data * s + b`, each rounded to 8 bits) decides the distance."""
    _, red, bshape = _layout(axis)
    stat_in = data.astype(jnp.float32)
    mean = jnp.mean(stat_in, axis=red)
    var = jnp.var(stat_in, axis=red)
    s = jax.lax.rsqrt(var + EPS) * gamma
    b = beta - mean * s
    out = data * s.astype(data.dtype).reshape(bshape) \
        + b.astype(data.dtype).reshape(bshape)
    return out, mean, var


def _f64(a):
    return np.asarray(jnp.asarray(a).astype(jnp.float32), np.float64)


def _worst(got, want):
    """Largest distance, as a share of the reference's largest entry."""
    got, want = _f64(got), _f64(want)
    return np.abs(got - want).max() / np.abs(want).max()


@pytest.mark.parametrize("moving", ["zero", "near"])
@pytest.mark.parametrize("offset", [0, 10, 100])
@pytest.mark.parametrize("axis", [1, -1])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_batchnorm_train_statistics_and_gradient(dtype, axis, offset, moving):
    """Float32 data takes two passes and is held, whatever the moving mean,
    to three times what that meets over a dozen seeds: 4e-6 of the variance
    at every offset, and of the rest (`base`) 5e-6, 9e-5 and 4e-4 at 0, 10
    and 100 spreads, all three read in gamma's gradient, whose cancellation
    in `x - mean` grows with the offset itself.

    bfloat16 data takes one pass. What its shift by the moving mean
    guarantees, with z the distance of the batch mean from the moving mean
    in spreads: the variance's relative error is t0 * (1 + z*z), since
    E[d^2] - E[d]^2 loses the digits of z*z. t0 = 2e-4: the data's few
    distinct values bias the CPU backend's running float32 sum (two passes
    read 3e-5 there). Once the moving mean has caught up (`near`: 0.98 of
    the batch mean, z = 0.02 * offset) that is under the data's own 2**-8 at
    every offset; at the first steps (`zero`) it is 2e-2 at 10 spreads and
    nothing at 100, where only sign, finiteness and the mean are held.
    Outputs and gradients follow the variance, so they get the same term."""
    rs = np.random.RandomState(zlib.crc32(
        ("%s %d %d %s" % (dtype, axis, offset, moving)).encode()))
    shape, red, bshape = _layout(axis)
    spread = rs.uniform(0.5, 2.0, CHANNELS)
    centre = offset * spread * np.array([1, -1, 1, -1])
    x = jnp.asarray(rs.normal(size=shape) * spread.reshape(bshape)
                    + centre.reshape(bshape), dtype)
    x64 = _f64(x)                       # the data as the operator sees it
    gamma = rs.uniform(0.5, 1.5, CHANNELS).astype(np.float32)
    beta = rs.normal(size=CHANNELS).astype(np.float32)
    moving_mean = np.zeros(CHANNELS, np.float32) if moving == "zero" \
        else (0.98 * x64.mean(axis=red)).astype(np.float32)
    moving_var = np.ones(CHANNELS, np.float32)
    ct = (jnp.asarray(rs.normal(size=shape), dtype),
          jnp.asarray(rs.normal(size=CHANNELS), jnp.float32),
          jnp.asarray(rs.normal(size=CHANNELS), jnp.float32))

    def run(data, g, b):
        return _bn(data, g, b, jnp.asarray(moving_mean),
                   jnp.asarray(moving_var), eps=EPS, fix_gamma=False,
                   axis=axis, _train=True)

    (out, mean, var), vjp = jax.vjp(run, x, jnp.asarray(gamma),
                                    jnp.asarray(beta))
    dx, dgamma, dbeta = vjp(ct)
    assert out.dtype == dx.dtype == x.dtype
    assert mean.dtype == var.dtype == dgamma.dtype == jnp.float32

    want = _reference(x64, gamma.astype(np.float64), beta.astype(np.float64),
                      red, bshape, *(_f64(c) for c in ct))
    std = np.sqrt(want[2])
    z2 = float(np.max(((want[1] - moving_mean) / std) ** 2))
    base = {0: 1.5e-5, 10: 3e-4, 100: 1.5e-3}[offset]
    t0 = 1.5e-5 if dtype == "float32" else 2e-4
    shift = 0 if dtype == "float32" else t0 * z2

    var = _f64(var)
    assert np.all(np.isfinite(var)) and np.all(var >= 0)
    assert np.max(np.abs(var - want[2]) / want[2]) <= t0 + shift
    assert np.all(np.abs(_f64(mean) - want[1])
                  <= t0 * (np.abs(want[1]) + std) * (1 + np.sqrt(z2)))

    names = ("out", "dx", "dgamma", "dbeta")
    got = dict(zip(names, (out, dx, dgamma, dbeta)))
    if dtype == "float32":
        want = dict(zip(names, (want[0],) + want[3:]))
        tol = base
    else:
        (o2, _, _), vjp2 = jax.vjp(lambda *a: _two_pass(*a, axis), x,
                                   jnp.asarray(gamma), jnp.asarray(beta))
        want = dict(zip(names, (o2,) + tuple(vjp2(ct))))
        # s and b may round to the next bfloat16: one step on a whole
        # channel, so the output is held by its operands' size, not its own
        tol = 2.0 ** -7 + shift
        scale = np.abs(x64).max() * np.abs(gamma / std).max() \
            + np.abs(_f64(o2)).max()
        assert np.abs(_f64(got.pop("out")) - _f64(o2)).max() <= tol * scale
    for name, value in got.items():
        assert _worst(value, want[name]) <= tol, name


def _walk(jaxpr, path=()):
    """Every equation of a jaxpr with the names of the calls around it."""
    for eqn in jaxpr.eqns:
        yield path, eqn
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    name = eqn.params.get("name", eqn.primitive.name)
                    yield from _walk(sub, path + (name,))


@pytest.mark.parametrize("dtype,n_reductions,two_pass", [
    # sum d and sum d*d forward; sum dy and sum dy*x backward
    ("bfloat16", 4, False),
    ("float16", 4, False),
    # `jnp.var` costs two more, both under its `_var` call: `mean((x -
    # mean)**2)` forward and the transpose of its inner mean backward, a
    # full read of the data to reduce a term that is zero. And float32 data
    # is applied as (x - mean) * scale + beta, so the mean's cotangent, sum
    # dy*scale, is a reduction of its own
    ("float32", 7, True),
])
def test_batchnorm_train_reductions_over_data(dtype, n_reductions, two_pass):
    """Half-precision data is read by four reductions, none under `_var`
    (six with `jnp.var`); float32 data keeps its seven."""
    shape, _, _ = _layout(1)
    x = jnp.ones(shape, dtype)
    vec = jnp.ones(CHANNELS, jnp.float32)
    ct = (x, vec, vec)

    def backward(data, g, b, ct):
        return jax.vjp(lambda *a: _bn(*a, vec, vec, fix_gamma=False,
                                      _train=True), data, g, b)[1](ct)

    eqns = list(_walk(jax.make_jaxpr(backward)(x, vec, vec, ct).jaxpr))
    over_data = [eqn for _, eqn in eqns
                 if eqn.primitive.name.startswith("reduce")
                 and any(getattr(v.aval, "shape", None) == shape
                         for v in eqn.invars)]
    assert len(over_data) == n_reductions, over_data
    assert any("_var" in path for path, _ in eqns) is two_pass
