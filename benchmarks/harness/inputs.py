"""Weights and input batches, made on the device from the seed.

One jitted call each. The same seed gives the same weights and the same
pool of batches, so a run's work is fixed by its seed.
"""
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

_MOD = 2 ** 31 - 1


def root_key(seed):
    """A PRNG key for any whole-number seed, also beyond 32 signed bits."""
    seed = int(seed)
    key = jax.random.key(seed % _MOD)
    return jax.random.fold_in(key, seed // _MOD)


def _init_leaf(key, name, shape, dtype):
    """MXNet's naming decides the rule: a convolution's ``*_weight`` is
    He-normal (std sqrt(2 / fan_in)), a classifier's (two axes) normal with
    std 0.01, so that the first logits are small and the first loss stands
    near log(classes); ``*_gamma`` and ``*_moving_var`` are 1, the rest
    (``*_beta``, ``*_bias``, ``*_moving_mean``) 0."""
    if name.endswith("_weight"):
        std = math.sqrt(2.0 / math.prod(shape[1:])) if len(shape) > 2 \
            else 0.01
        return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)
    if name.endswith(("_gamma", "_moving_var")):
        return jnp.ones(shape, dtype)
    return jnp.zeros(shape, dtype)


@functools.partial(jax.jit, static_argnums=(1,))
def _make_weights(key, leaves):
    keys = jax.random.split(key, len(leaves))
    return {name: _init_leaf(k, name, shape, jnp.dtype(dtype))
            for k, (name, (shape, dtype)) in zip(keys, leaves)}


def make_weights(seed, leaves):
    """``{name: array}`` for ``leaves`` (a dict ``name -> (shape, dtype
    name)``), each in the type it is computed in, from ``seed``."""
    key = jax.random.fold_in(root_key(seed), 1)
    return _make_weights(key, tuple(sorted(leaves.items())))


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _make_batch(key, shape, classes, dtype):
    kx, ky = jax.random.split(key)
    x = jax.random.uniform(kx, shape, jnp.float32, -1.0, 1.0).astype(dtype)
    y = jax.random.randint(ky, shape[:1], 0, classes).astype(jnp.float32)
    return x, y


def make_pool(seed, n, batch_shape, classes, dtype):
    """``n`` distinct host batches ``(data, label)`` as numpy arrays:
    data uniform in [-1, 1] in ``dtype``, labels uniform over the classes
    as float32."""
    key = jax.random.fold_in(root_key(seed), 2)
    pool = []
    for k in jax.random.split(key, n):
        x, y = _make_batch(k, tuple(batch_shape), classes, jnp.dtype(dtype))
        pool.append((np.asarray(x), np.asarray(y)))
    return pool
