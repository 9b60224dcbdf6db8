"""The routed layer's combine as a Pallas TPU kernel: a token's row is the
sum of the rows its held selections were sorted to, and only those rows
are read.

``parallel/moe.py`` sorts a step's ``T k`` selections by expert, those of
experts held elsewhere last, so the first ``total`` rows of the sorted
buffer ``y`` are real and selection s is held here where its sorted
position ``inv[s] < total``. The plain combine gathers all ``T k`` rows
into a buffer, masks the rows of selections held elsewhere and adds a
token's k rows in float32: a pass over all ``T k`` rows, of which the
held share is an eighth to a thirty-second on the benchmark's cells, and
a second pass that reads the gathered buffer again.

``moe_combine`` leaves ``y`` in HBM. A program of the grid takes ``TILE``
tokens: it lists their held selections in slot order in SMEM (a selection
held elsewhere starts no copy), keeps ``RING`` copies in flight and, as
each lands, adds its row to the token's float32 row in VMEM; the tile is
rounded to ``y``'s type once and written. A copy from HBM cannot start or
end inside one of the array's tiles of ``ROWS`` rows, so it takes the tile
that holds the row, and the row alone is taken from it: no value of
another row, a row past ``total`` (undefined on the chip) included, comes
into the sum. A token's rows are added in slot order ``j = 0 .. k - 1``
from nought, a slot held elsewhere adding nothing: the plain combine's
arithmetic, bit for bit.

The kernel is a primitive of its own, lowered as the compiled kernel for
the TPU and interpreted elsewhere; a program lowered for the TPU from a
process without one (a compile for a described chip) holds the kernel.
"""
from __future__ import annotations

import functools

import jax
import jax.extend.core
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.interpreters import mlir

__all__ = ["moe_combine"]

#: tokens a program takes: (256, 3,072) float32 rows are 3 MB of VMEM
TILE = 256
#: copies in flight (on the chip 8, 16 and 32 read within 3 % of one another)
RING = 16
#: rows of an HBM tile: where a copy may start, and the fewest it may take
ROWS = 8


def _kernel(total_ref, inv_ref, y_hbm, o_ref, ring, acc, held, sem, *, k):
    tile = o_ref.shape[0]
    total = total_ref[0]
    bits = max(k - 1, 1).bit_length()

    # the held selections of the tile, in slot order: token << bits | slot
    def note(t, n):
        real = [inv_ref[0, t * k + j] < total for j in range(k)]
        for j, h in enumerate(real):
            held[n] = (t << bits) | j
            n = n + h.astype(jnp.int32)
        return n

    n = lax.fori_loop(0, tile, note, 0)

    def row(e):
        v = held[e]
        return inv_ref[0, (v >> bits) * k + (v & (1 << bits) - 1)], v >> bits

    def copy(e):
        start = pl.multiple_of(row(e)[0] // ROWS * ROWS, ROWS)
        return pltpu.make_async_copy(y_hbm.at[pl.ds(start, ROWS)],
                                     ring.at[e % RING], sem.at[e % RING])

    def start(e, c):
        copy(e).start()
        return c

    lax.fori_loop(0, jnp.minimum(n, RING), start, 0)
    acc[...] = jnp.zeros(acc.shape, acc.dtype)

    def add(e, c):
        copy(e).wait()
        r, t = row(e)
        block = ring[e % RING].astype(jnp.float32)
        pick = lax.broadcasted_iota(jnp.int32, block.shape, 0) == r % ROWS
        acc[pl.ds(t, 1), :] += jnp.sum(jnp.where(pick, block, 0.0), axis=0,
                                       keepdims=True)

        @pl.when(e + RING < n)
        def _():
            copy(e + RING).start()
        return c

    lax.fori_loop(0, n, add, 0)
    o_ref[...] = acc[:tile].astype(o_ref.dtype)


def _call(y, inv, total, *, k, interpret):
    rows, width = y.shape
    t = inv.shape[0] // k
    tile = min(t, TILE)
    grid = -(-t // tile)
    # the last program's tokens past the last one are held nowhere
    inv = jnp.pad(inv, (0, grid * tile * k - inv.size),
                  constant_values=jnp.iinfo(jnp.int32).max)
    # a copy takes whole tiles of rows
    y = jnp.pad(y, ((0, -rows % ROWS), (0, 0)))
    return pl.pallas_call(
        functools.partial(_kernel, k=k),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(grid,),
            in_specs=[pl.BlockSpec((None, 1, tile * k),
                                   lambda i, _: (i, 0, 0),
                                   memory_space=pltpu.SMEM),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((tile, width), lambda i, _: (i, 0)),
            scratch_shapes=[pltpu.VMEM((RING, ROWS, width), y.dtype),
                            pltpu.VMEM((-(-tile // ROWS) * ROWS, width),
                                       jnp.float32),
                            pltpu.SMEM((tile * k,), jnp.int32),
                            pltpu.SemaphoreType.DMA((RING,))]),
        out_shape=jax.ShapeDtypeStruct((t, width), y.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        name="moe_combine", interpret=interpret,
    )(total.reshape(1), inv.reshape(grid, 1, -1), y)


_combine = jax.extend.core.Primitive("moe_combine")
_combine.def_abstract_eval(lambda y, inv, total, *, k: jax.core.ShapedArray(
    (inv.shape[0] // k, y.shape[1]), y.dtype))
_combine.def_impl(lambda *a, k: _call(
    *a, k=k, interpret=jax.default_backend() != "tpu"))
mlir.register_lowering(_combine, mlir.lower_fun(
    functools.partial(_call, interpret=True), multiple_results=False))
mlir.register_lowering(_combine, mlir.lower_fun(
    functools.partial(_call, interpret=False), multiple_results=False),
    platform="tpu")


def moe_combine(y, inv, total, k):
    """``(T, width)`` in ``y``'s type: token t's row is the sum, in float32
    and in slot order, of the rows of ``y`` (rows, width) at ``inv[t k ..
    t k + k - 1]`` (int32) that lie before ``total`` (an int32 scalar);
    a slot at or past ``total`` adds nought and its row is not read."""
    return _combine.bind(y, inv, jnp.asarray(total, jnp.int32), k=k)
