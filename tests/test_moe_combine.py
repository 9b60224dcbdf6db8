"""The routed layer's combine kernel (``pallas/moe_combine.py``, run
interpreted here) against the plain formula it replaces: gather the rows
the selections were sorted to, take nought where a selection's row lies
past the held ones, add a token's k rows in float32 and round once. Bit
for bit, and the rows past the held ones are NaN: nothing is taken from
them."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu.pallas import moe_combine as mc


def _plain(y, inv, total, k):
    rows = jnp.where((inv < total)[:, None], jnp.take(y, inv, axis=0), 0)
    return jnp.sum(rows.reshape((-1, k) + y.shape[1:]).astype(jnp.float32),
                   axis=1).astype(y.dtype)


#: tokens: two programs of the grid, the second one part full
T = mc.TILE + 44

_CASES = [
    # id, k, held rows (None: every selection), one token's k all held
    ("no_row_held", 8, 0, False),
    ("one_token_holds_all_k", 8, 8, True),
    ("ends_mid_tile", 6, 1003, False),
    ("ends_at_a_chunk_boundary", 4, 512, False),
    ("every_selection_held", 8, None, False),
]


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", _CASES, ids=lambda c: c[0])
def test_kernel_is_the_plain_combine(case, dtype):
    _, k, total, whole_token = case
    n = T * k
    total = n if total is None else total
    rs = np.random.RandomState(k * 1000 + total)
    inv = rs.permutation(n).astype(np.int32)
    if whole_token:
        # token 5's slots take the held rows 0 .. k - 1
        at = np.flatnonzero(inv < k)
        inv[at], inv[5 * k:6 * k] = inv[5 * k:6 * k], inv[at].copy()
        assert sorted(inv[5 * k:6 * k]) == list(range(k))
    # the sorted buffer in whole chunks of 512 rows; the rows past the
    # held ones hold NaN, as undefined rows may on the chip
    rows = -(-n // 512) * 512
    y = rs.standard_normal((rows, 128)).astype(np.float32)
    y[total:] = np.nan
    y = jnp.asarray(y).astype(dtype)
    inv = jnp.asarray(inv)

    got = jax.jit(lambda y, inv, total: mc.moe_combine(y, inv, total, k))(
        y, inv, jnp.int32(total))
    want = _plain(y, inv, total, k)
    assert got.shape == (T, 128) and got.dtype == y.dtype
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert np.isfinite(got).all()
    np.testing.assert_array_equal(got, want)
    held = (np.asarray(inv) < total).reshape(T, k).any(1)
    assert (got[~held] == 0).all() and (got[held] != 0).any() == held.any()
