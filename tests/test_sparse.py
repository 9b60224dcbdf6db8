"""Sparse NDArray + sparse training path tests (parity model: reference
tests/python/unittest/test_sparse_ndarray.py / test_sparse_operator.py /
test_optimizer.py sparse sections)."""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.ndarray import sparse as sp


def test_row_sparse_roundtrip():
    data = np.array([[1., 2.], [3., 4.]], np.float32)
    rsp = sp.row_sparse_array((data, [1, 3]), shape=(5, 2))
    assert rsp.stype == "row_sparse"
    dense = rsp.asnumpy()
    expect = np.zeros((5, 2), np.float32)
    expect[[1, 3]] = data
    np.testing.assert_allclose(dense, expect)
    back = sp.cast_storage(mx.nd.array(expect), "row_sparse")
    np.testing.assert_allclose(back.data.asnumpy(), data)
    np.testing.assert_allclose(back.indices.asnumpy(), [1, 3])


def test_csr_roundtrip():
    dense = np.array([[0, 1, 0], [2, 0, 3], [0, 0, 0]], np.float32)
    csr = sp.cast_storage(mx.nd.array(dense), "csr")
    assert csr.stype == "csr"
    np.testing.assert_allclose(csr.asnumpy(), dense)
    np.testing.assert_allclose(csr.data.asnumpy(), [1, 2, 3])
    np.testing.assert_allclose(csr.indices.asnumpy(), [1, 0, 2])
    np.testing.assert_allclose(csr.indptr.asnumpy(), [0, 1, 3, 3])
    # tostype round trip
    np.testing.assert_allclose(csr.tostype("row_sparse").asnumpy(), dense)


def test_retain():
    rsp = sp.row_sparse_array((np.ones((3, 2), np.float32), [0, 2, 4]),
                              shape=(6, 2))
    kept = rsp.retain([2, 4, 5])
    np.testing.assert_allclose(kept.indices.asnumpy(), [2, 4])
    assert kept.shape == (6, 2)


def test_add_n_union_of_rows():
    a = sp.row_sparse_array((np.array([[1., 1.], [2., 2.]]), [0, 2]),
                            shape=(4, 2))
    b = sp.row_sparse_array((np.array([[10., 10.], [20., 20.]]), [2, 3]),
                            shape=(4, 2))
    s = sp.add_n([a, b])
    assert s.stype == "row_sparse"
    np.testing.assert_allclose(s.indices.asnumpy(), [0, 2, 3])
    expect = np.zeros((4, 2))
    expect[0] = 1
    expect[2] = [12, 12]
    expect[3] = [20, 20]
    np.testing.assert_allclose(s.asnumpy(), expect)


def test_sparse_dot():
    rng = np.random.RandomState(0)
    dense = rng.normal(size=(4, 6)).astype(np.float32)
    dense[dense < 0.5] = 0
    rhs = rng.normal(size=(6, 3)).astype(np.float32)
    csr = sp.cast_storage(mx.nd.array(dense), "csr")
    out = sp.dot(csr, mx.nd.array(rhs))
    np.testing.assert_allclose(out.asnumpy(), dense @ rhs, rtol=1e-5,
                               atol=1e-5)
    # transpose_a: csr^T . dense — the sparse-linear-regression grad path
    out_t = sp.dot(csr, mx.nd.array(rng.normal(size=(4, 3))
                                    .astype(np.float32)), transpose_a=True)
    assert out_t.shape == (6, 3)


def _lazy_rows_check(opt_name, **kwargs):
    """Rows absent from a row_sparse grad must stay untouched."""
    opt = mx.optimizer.create(opt_name, learning_rate=0.1, **kwargs)
    w = mx.nd.array(np.ones((5, 3), np.float32))
    state = opt.create_state(0, w)
    grad = sp.row_sparse_array((np.full((2, 3), 0.5, np.float32), [1, 3]),
                               shape=(5, 3))
    w_before = w.asnumpy().copy()
    opt.update(0, w, grad, state)
    w_after = w.asnumpy()
    untouched = [0, 2, 4]
    np.testing.assert_allclose(w_after[untouched], w_before[untouched])
    assert np.all(w_after[[1, 3]] != w_before[[1, 3]])
    return w_after


def test_sgd_lazy_update():
    w = _lazy_rows_check("sgd", momentum=0.9)
    # exact value: mom=0 -> m = -lr*g = -0.05; w = 1 - 0.05
    np.testing.assert_allclose(w[[1, 3]], 0.95, rtol=1e-6)


def test_sgd_lazy_no_momentum():
    w = _lazy_rows_check("sgd")
    np.testing.assert_allclose(w[[1, 3]], 0.95, rtol=1e-6)


def test_adam_lazy_update():
    _lazy_rows_check("adam")


def test_adagrad_lazy_update():
    _lazy_rows_check("adagrad")


def test_kvstore_row_sparse_push_pull():
    kv = mx.kv.create("device")
    kv.init("emb", mx.nd.zeros((6, 2)))
    g1 = sp.row_sparse_array((np.ones((2, 2), np.float32), [0, 2]),
                             shape=(6, 2))
    g2 = sp.row_sparse_array((np.full((1, 2), 3.0, np.float32), [2]),
                             shape=(6, 2))
    kv.push("emb", [g1, g2])
    out = sp.zeros("row_sparse", (6, 2))
    kv.row_sparse_pull("emb", out=out, row_ids=mx.nd.array([0, 2]))
    expect = np.zeros((6, 2), np.float32)
    expect[0] = 1
    expect[2] = 4
    np.testing.assert_allclose(out.asnumpy(), expect)


def test_kvstore_mixed_sparse_dense_push():
    """Mixed shard lists fall back to a dense sum keeping every
    contribution."""
    kv = mx.kv.create("local")
    kv.init(0, mx.nd.zeros((4, 2)))
    rsp = sp.row_sparse_array((np.ones((1, 2), np.float32), [1]),
                              shape=(4, 2))
    dense = mx.nd.ones((4, 2))
    kv.push(0, [rsp, dense])
    out = mx.nd.zeros((4, 2))
    kv.pull(0, out=out)
    expect = np.ones((4, 2), np.float32)
    expect[1] += 1
    np.testing.assert_allclose(out.asnumpy(), expect)


def test_compression_rejects_sparse():
    kv = mx.kv.create("local")
    kv.set_gradient_compression({"type": "2bit"})
    kv.init(0, mx.nd.zeros((4, 2)))
    rsp = sp.row_sparse_array((np.ones((1, 2), np.float32), [1]),
                              shape=(4, 2))
    with pytest.raises(mx.MXNetError):
        kv.push(0, [rsp])


def test_sgd_multi_precision_sparse():
    """fp16 weight + fp32 master copy with a row_sparse grad (reference
    MP_SGD row_sparse kernels)."""
    opt = mx.optimizer.create("sgd", learning_rate=0.1, momentum=0.9,
                              multi_precision=True)
    w = mx.nd.array(np.ones((5, 3)), dtype="float16")
    state = opt.create_state_multi_precision(0, w)
    assert isinstance(state, tuple)
    grad = sp.row_sparse_array((np.full((2, 3), 0.5, np.float32), [1, 3]),
                               shape=(5, 3))
    opt.update_multi_precision(0, w, grad, state)
    w_after = w.asnumpy()
    assert w.dtype == np.float16
    np.testing.assert_allclose(w_after[[0, 2, 4]], 1.0)
    np.testing.assert_allclose(w_after[[1, 3]], 0.95, rtol=1e-3)
    # master copy stays fp32 and matches
    np.testing.assert_allclose(state[1].asnumpy()[[1, 3]], 0.95, rtol=1e-6)


def test_sparse_grad_stays_sparse_through_kvstore():
    """Aggregation must not densify (the merged store value is rsp)."""
    kv = mx.kv.create("local")
    kv.init(0, mx.nd.zeros((4, 2)))
    g = sp.row_sparse_array((np.ones((1, 2), np.float32), [1]), shape=(4, 2))
    kv.push(0, [g, g])
    assert isinstance(kv._store[0], sp.RowSparseNDArray)
    np.testing.assert_allclose(kv._store[0].data.asnumpy(), [[2., 2.]])


def test_csr_dot_native_vs_numpy():
    """csr . dense and csr^T . dense run on the compressed representation
    (reference dot-inl.h sparse kernels); checked against numpy on random
    matrices with empty rows."""
    rs = np.random.RandomState(3)
    dense = rs.uniform(-1, 1, (17, 9)).astype(np.float32)
    dense[dense < 0.4] = 0          # ~70% sparse
    dense[5] = 0                    # fully empty row
    dense[12] = 0
    csr = mx.nd.sparse.csr_matrix(dense)
    rhs = rs.uniform(-1, 1, (9, 4)).astype(np.float32)
    rhs_t = rs.uniform(-1, 1, (17, 4)).astype(np.float32)

    out = mx.nd.sparse.dot(csr, mx.nd.array(rhs))
    assert out.stype == "default"
    np.testing.assert_allclose(out.asnumpy(), dense @ rhs, rtol=1e-5,
                               atol=1e-6)
    out_t = mx.nd.sparse.dot(csr, mx.nd.array(rhs_t), transpose_a=True)
    np.testing.assert_allclose(out_t.asnumpy(), dense.T @ rhs_t, rtol=1e-5,
                               atol=1e-6)


def test_cast_storage_csr_vectorized_roundtrip():
    rs = np.random.RandomState(4)
    dense = rs.uniform(-1, 1, (31, 23)).astype(np.float32)
    dense[dense < 0.5] = 0
    dense[0] = 0                     # leading empty row
    dense[-1] = 0                    # trailing empty row
    csr = mx.nd.sparse.csr_matrix(dense)
    # canonical CSR invariants
    ptr = csr.indptr.asnumpy()
    assert ptr[0] == 0 and ptr[-1] == csr.data.shape[0]
    assert (np.diff(ptr) >= 0).all()
    np.testing.assert_allclose(csr.tostype("default").asnumpy(), dense)
    # columns sorted within each row (row-major nonzero order)
    ind = csr.indices.asnumpy()
    for r in range(31):
        row = ind[ptr[r]:ptr[r + 1]]
        assert (np.diff(row) > 0).all() if len(row) > 1 else True


def test_retain_device_side():
    data = np.arange(12, dtype=np.float32).reshape(4, 3)
    rsp = mx.nd.sparse.row_sparse_array(
        (data, [1, 3, 5, 8]), shape=(10, 3))
    kept = rsp.retain(mx.nd.array(np.array([3, 8, 9], np.float32)))
    np.testing.assert_array_equal(kept.indices.asnumpy(), [3, 8])
    np.testing.assert_allclose(kept.data.asnumpy(), data[[1, 3]])
    # dense view agrees
    want = np.zeros((10, 3), np.float32)
    want[3] = data[1]
    want[8] = data[3]
    np.testing.assert_allclose(kept.tostype("default").asnumpy(), want)


def test_csr_dot_empty_matrix():
    csr = mx.nd.sparse.zeros("csr", (5, 7))
    rhs = mx.nd.array(np.ones((7, 2), np.float32))
    out = mx.nd.sparse.dot(csr, rhs)
    np.testing.assert_allclose(out.asnumpy(), np.zeros((5, 2)))


def test_csr_dot_shape_mismatch_raises():
    csr = mx.nd.sparse.csr_matrix(np.eye(4, 6, dtype=np.float32))
    with pytest.raises(mx.MXNetError):
        sp.dot(csr, mx.nd.array(np.ones((5, 2), np.float32)))
    with pytest.raises(mx.MXNetError):
        sp.dot(csr, mx.nd.array(np.ones((6, 2), np.float32)),
               transpose_a=True)


def test_csr_dot_vector_rhs_falls_back_dense():
    dense = np.eye(4, 6, dtype=np.float32) * 2
    csr = mx.nd.sparse.csr_matrix(dense)
    v = np.arange(6, dtype=np.float32)
    out = sp.dot(csr, mx.nd.array(v))
    np.testing.assert_allclose(out.asnumpy(), dense @ v)


def test_csr_elemwise_add():
    """csr + csr keeps csr storage (reference elemwise add with the
    storage-fallback path for kernel-less combinations)."""
    d = np.random.RandomState(0).uniform(size=(4, 6)).astype(np.float32)
    d[d < 0.5] = 0
    c = sp.csr_matrix(d)
    s = sp.elemwise_add(c, c)
    assert s.stype == "csr"
    np.testing.assert_allclose(s.asnumpy(), 2 * d, rtol=1e-6)


def test_cast_storage_sparse_to_sparse_native():
    """rsp<->csr conversions run on the compressed representation —
    correct for unsorted rsp indices and explicit zeros inside stored
    rows, and the input's dense cache must stay cold (no densify).
    Parity: reference cast_storage-inl.h sparse-to-sparse paths."""
    # unsorted indices + a zero inside a stored row + an all-zero row
    data = np.array([[0., 5., 0.], [1., 0., 2.], [0., 0., 0.]], np.float32)
    rsp = sp.row_sparse_array((data, [4, 1, 2]), shape=(6, 3))
    csr = rsp.tostype("csr")
    back = csr.tostype("row_sparse")
    # both conversions ran before any dense access: caches stay cold
    assert rsp._dense_cache is None
    assert csr._dense_cache is None
    expect = np.zeros((6, 3), np.float32)
    expect[[4, 1, 2]] = data
    np.testing.assert_allclose(csr.asnumpy(), expect)
    np.testing.assert_allclose(csr.indptr.asnumpy(),
                               [0, 0, 2, 2, 2, 3, 3])
    # all-zero stored row 2 disappears; row order is sorted
    np.testing.assert_allclose(back.indices.asnumpy(), [1, 4])
    np.testing.assert_allclose(back.data.asnumpy(),
                               [[1., 0., 2.], [0., 5., 0.]])


def test_csr_dot_backward_native():
    """Autograd through the native csr.dot path: grad w.r.t. the dense
    rhs is the transposed O(nnz) kernel, and the csr lhs is never
    densified (reference dot-inl.h fwd/bwd kernel pair)."""
    from mxnet_tpu import autograd
    rng = np.random.RandomState(3)
    lhs = ((rng.rand(6, 5) < 0.4) * rng.randn(6, 5)).astype(np.float32)
    csr = sp.cast_storage(mx.nd.array(lhs), "csr")
    csr._dense_cache = None  # cast from dense caches; reset for the probe
    w = mx.nd.array(rng.randn(5, 4).astype(np.float32))
    w.attach_grad()
    with autograd.record():
        out = sp.dot(csr, w)
        loss = (out * out).sum()
    loss.backward()
    # d/dW sum((A W)^2) = 2 A^T (A W)
    expect = 2.0 * lhs.T @ (lhs @ np.asarray(w.asnumpy()))
    np.testing.assert_allclose(w.grad.asnumpy(), expect, rtol=1e-5,
                               atol=1e-5)
    assert csr._dense_cache is None

    # transpose_a path: d/dW sum((A^T W)^2) = 2 A (A^T W)
    w2 = mx.nd.array(rng.randn(6, 3).astype(np.float32))
    w2.attach_grad()
    with autograd.record():
        out2 = sp.dot(csr, w2, transpose_a=True)
        loss2 = (out2 * out2).sum()
    loss2.backward()
    expect2 = 2.0 * lhs @ (lhs.T @ np.asarray(w2.asnumpy()))
    np.testing.assert_allclose(w2.grad.asnumpy(), expect2, rtol=1e-5,
                               atol=1e-5)
    assert csr._dense_cache is None


def _live_device_bytes():
    import jax
    return sum(int(np.prod(a.shape)) * np.dtype(a.dtype).itemsize
               for a in jax.live_arrays())


def test_sparse_embedding_scale_o_nnz_memory():
    """The SURVEY §2.3 case: a 1M x 512 embedding gradient. Every sparse
    op in the chain (add_n, retain, rsp->csr->rsp) must stay O(nnz +
    nrows-metadata): live device bytes may grow by a small fraction of
    the 2 GB dense shape, and no dense cache may be populated."""
    NROWS, NCOLS, NNZ = 1_000_000, 512, 1024
    dense_bytes = NROWS * NCOLS * 4
    rng = np.random.RandomState(0)
    rows = np.unique(rng.randint(0, NROWS, NNZ * 2))[:NNZ].astype(np.int64)
    vals = rng.randn(len(rows), NCOLS).astype(np.float32)
    base = _live_device_bytes()
    g1 = sp.row_sparse_array((vals, rows), shape=(NROWS, NCOLS))
    g2 = sp.row_sparse_array((vals * 2.0, rows), shape=(NROWS, NCOLS))
    s = sp.add_n([g1, g2])
    kept = s.retain(rows[:16].tolist())
    csr = s.tostype("csr")
    back = csr.tostype("row_sparse")
    import jax
    jax.block_until_ready(back._rsp_data)
    grown = _live_device_bytes() - base
    assert grown < dense_bytes // 10, \
        "sparse chain allocated %d bytes (dense would be %d)" % (
            grown, dense_bytes)
    for a in (g1, g2, s, kept, csr, back):
        assert a._dense_cache is None
    # spot-check values without densifying
    np.testing.assert_allclose(s.data.asnumpy(), vals * 3.0, rtol=1e-6)
    np.testing.assert_allclose(back.indices.asnumpy(), rows)
    np.testing.assert_allclose(back.data.asnumpy(), vals * 3.0, rtol=1e-6)
    np.testing.assert_allclose(kept.indices.asnumpy(), rows[:16])


def test_cast_storage_duplicate_rsp_rows_matches_dense_view():
    """Duplicate row ids in a user-built rsp: the csr conversion must
    agree with the dense view's scatter-set semantics (last stored
    occurrence wins), not scatter values into unrelated rows."""
    rsp = sp.row_sparse_array(
        (np.array([[1., 2.], [3., 4.], [5., 0.]], np.float32), [1, 1, 3]),
        shape=(5, 2))
    dense = rsp.asnumpy()
    np.testing.assert_allclose(dense[1], [3., 4.])  # last wins
    csr = sp.row_sparse_array(
        (np.array([[1., 2.], [3., 4.], [5., 0.]], np.float32), [1, 1, 3]),
        shape=(5, 2)).tostype("csr")
    np.testing.assert_allclose(csr.asnumpy(), dense)


def test_csr_elemwise_add_native_no_densify():
    """csr + csr merges on the compressed representation: correct for
    overlapping and disjoint coordinates, never materialises dense, and
    stays O(nnz) at the 1M x 512 embedding scale."""
    rs = np.random.RandomState(7)
    a_dense = (rs.rand(6, 5) < 0.4) * rs.randn(6, 5)
    b_dense = (rs.rand(6, 5) < 0.4) * rs.randn(6, 5)
    a = sp.csr_matrix(a_dense.astype(np.float32))
    b = sp.csr_matrix(b_dense.astype(np.float32))
    a._dense_cache = None
    b._dense_cache = None
    out = sp.elemwise_add(a, b)
    assert a._dense_cache is None and b._dense_cache is None
    np.testing.assert_allclose(out.asnumpy(),
                               (a_dense + b_dense).astype(np.float32),
                               rtol=1e-6)

    # scale: live device bytes stay O(nnz), not O(1M x 512)
    NROWS, NCOLS, NNZ = 1_000_000, 512, 2048
    rows = np.sort(rs.choice(NROWS, NNZ, replace=False)).astype(np.int64)
    cols = rs.randint(0, NCOLS, NNZ).astype(np.int64)
    # CSR construction wants per-row sorted cols; build via indptr
    order = np.lexsort((cols, rows))
    rows, cols = rows[order], cols[order]
    counts = np.bincount(rows, minlength=NROWS)
    indptr = np.concatenate([[0], np.cumsum(counts)])
    vals = rs.randn(NNZ).astype(np.float32)
    big_a = sp.CSRNDArray(vals, cols, indptr, (NROWS, NCOLS))
    big_b = sp.CSRNDArray(vals * 2.0, cols, indptr, (NROWS, NCOLS))
    base = _live_device_bytes()
    big = sp.elemwise_add(big_a, big_b)
    import jax
    jax.block_until_ready(big._csr_data)
    grown = _live_device_bytes() - base
    assert grown < (NROWS * NCOLS * 4) // 10, grown
    assert big._dense_cache is None
    np.testing.assert_allclose(np.asarray(big._csr_data), vals * 3.0,
                               rtol=1e-6)


# ---------------------------------------------------------------------------
# round-5 native kernel set: sub/mul, scalar ops, square, _square_sum,
# sum(csr, axis) — the remaining reference FComputeEx table
# (elemwise_binary_op_basic.cc, elemwise_binary_scalar_op_basic.cc,
# elemwise_unary_op_basic.cc square, square_sum-inl.h,
# broadcast_reduce_op_value.cc).
# ---------------------------------------------------------------------------

def _rand_sparse_pair(rs, shape, density=0.4):
    a = ((rs.rand(*shape) < density) * rs.randn(*shape)).astype(np.float32)
    b = ((rs.rand(*shape) < density) * rs.randn(*shape)).astype(np.float32)
    return a, b


@pytest.mark.parametrize("op,npop", [
    ("elemwise_sub", np.subtract), ("elemwise_mul", np.multiply)])
@pytest.mark.parametrize("stype", ["csr", "row_sparse"])
def test_elemwise_sub_mul_native(op, npop, stype):
    rs = np.random.RandomState(11)
    ad, bd = _rand_sparse_pair(rs, (7, 5))
    a = sp.csr_matrix(ad) if stype == "csr" else sp.row_sparse_array(ad)
    b = sp.csr_matrix(bd) if stype == "csr" else sp.row_sparse_array(bd)
    a._dense_cache = None
    b._dense_cache = None
    out = getattr(sp, op)(a, b)
    assert out.stype == stype          # reference storage table
    assert a._dense_cache is None and b._dense_cache is None
    assert out._dense_cache is None
    np.testing.assert_allclose(out.asnumpy(), npop(ad, bd), rtol=1e-6)


@pytest.mark.parametrize("stype", ["csr", "row_sparse"])
def test_elemwise_dispatch_via_registered_ops(stype):
    """mx.nd.elemwise_* and the NDArray dunders route sparse/sparse
    pairs through the native kernels — the FInferStorageType dispatch,
    not the python sparse module only."""
    rs = np.random.RandomState(12)
    ad, bd = _rand_sparse_pair(rs, (6, 4))
    mk = sp.csr_matrix if stype == "csr" else sp.row_sparse_array
    a, b = mk(ad), mk(bd)
    for fn, ref in [(mx.nd.elemwise_add, ad + bd),
                    (mx.nd.elemwise_sub, ad - bd),
                    (mx.nd.elemwise_mul, ad * bd),
                    (lambda x, y: x - y, ad - bd),
                    (lambda x, y: x * y, ad * bd)]:
        a._dense_cache = None
        b._dense_cache = None
        out = fn(a, b)
        assert out.stype == stype, fn
        assert a._dense_cache is None and b._dense_cache is None
        np.testing.assert_allclose(out.asnumpy(), ref, rtol=1e-6)


def test_scalar_ops_preserve_stype():
    """_mul_scalar/_div_scalar operate on the data array only (reference
    `only operates on data array of input if input is sparse`);
    plus_scalar produces dense (reference WITH_DENSE_RESULT macro)."""
    rs = np.random.RandomState(13)
    ad = ((rs.rand(5, 3) < 0.5) * rs.randn(5, 3)).astype(np.float32)
    for mk, stype in [(sp.csr_matrix, "csr"),
                      (sp.row_sparse_array, "row_sparse")]:
        arr = mk(ad)
        arr._dense_cache = None
        out = arr * 2.5
        assert out.stype == stype
        assert arr._dense_cache is None
        np.testing.assert_allclose(out.asnumpy(), ad * 2.5, rtol=1e-6)
        out = arr / 2.0
        assert out.stype == stype
        np.testing.assert_allclose(out.asnumpy(), ad / 2.0, rtol=1e-6)
        out = -arr
        assert out.stype == stype
        np.testing.assert_allclose(out.asnumpy(), -ad, rtol=1e-6)
        dense_out = arr + 1.0           # f(0) != 0 -> dense result
        assert dense_out.stype == "default"
        np.testing.assert_allclose(dense_out.asnumpy(), ad + 1.0, rtol=1e-6)


def test_square_preserves_stype():
    rs = np.random.RandomState(14)
    ad = ((rs.rand(6, 3) < 0.5) * rs.randn(6, 3)).astype(np.float32)
    for mk, stype in [(sp.csr_matrix, "csr"),
                      (sp.row_sparse_array, "row_sparse")]:
        arr = mk(ad)
        arr._dense_cache = None
        out = mx.nd.square(arr)
        assert out.stype == stype
        assert arr._dense_cache is None and out._dense_cache is None
        np.testing.assert_allclose(out.asnumpy(), ad * ad, rtol=1e-6)


def test_square_sum_storage_table():
    """_square_sum storage rules (square_sum-inl.h
    SquareSumForwardInferStorageType): axis=1+keepdims -> rsp;
    axis=1 -> dense vector; axis=0 -> dense."""
    data = np.array([[1., 2.], [0., 3.]], np.float32)
    rows = [1, 4]
    rsp = sp.row_sparse_array((data, rows), shape=(6, 2))
    dense = rsp.asnumpy()

    out = sp.square_sum(rsp, axis=1, keepdims=True)
    assert out.stype == "row_sparse" and out.shape == (6, 1)
    np.testing.assert_allclose(out.indices.asnumpy(), rows)
    np.testing.assert_allclose(out.asnumpy(),
                               (dense ** 2).sum(axis=1, keepdims=True))

    out = sp.square_sum(rsp, axis=1)
    assert out.stype == "default"
    np.testing.assert_allclose(out.asnumpy(), (dense ** 2).sum(axis=1))

    out = sp.square_sum(rsp, axis=0)
    assert out.stype == "default"
    np.testing.assert_allclose(out.asnumpy(), (dense ** 2).sum(axis=0))

    # registered-op route (reference mx.nd._internal._square_sum call
    # site, square_sum.cc:39)
    out = mx.nd._square_sum(rsp, axis=1, keepdims=True)
    assert out.stype == "row_sparse"
    # dense input has no kernel in the reference either
    with pytest.raises(mx.MXNetError):
        mx.nd._square_sum(mx.nd.array(dense))


def test_sum_csr_axis_native():
    """sum(csr, axis=0/1) reduces on the compressed representation
    (broadcast_reduce_op_value.cc csr FComputeEx), dense output."""
    rs = np.random.RandomState(15)
    ad = ((rs.rand(6, 5) < 0.4) * rs.randn(6, 5)).astype(np.float32)
    csr = sp.csr_matrix(ad)
    csr._dense_cache = None
    for kwargs, ref in [({"axis": 1}, ad.sum(axis=1)),
                        ({"axis": 0}, ad.sum(axis=0)),
                        ({"axis": 1, "keepdims": True},
                         ad.sum(axis=1, keepdims=True)),
                        ({"axis": 0, "keepdims": True},
                         ad.sum(axis=0, keepdims=True))]:
        out = mx.nd.sum(csr, **kwargs)
        assert out.stype == "default"
        assert csr._dense_cache is None, kwargs
        np.testing.assert_allclose(out.asnumpy(), ref, rtol=1e-5)


def test_native_kernels_no_densify_at_scale():
    """The round-5 kernel set at 1M x 512: sub, mul, scalar-mul, square,
    _square_sum chained on rsp inputs grow live device bytes by O(nnz),
    never the 2 GB dense shape; csr sub/mul/sum at the same scale."""
    import jax
    NROWS, NCOLS, NNZ = 1_000_000, 512, 1024
    dense_bytes = NROWS * NCOLS * 4
    rs = np.random.RandomState(16)
    rows = np.unique(rs.randint(0, NROWS, NNZ * 2))[:NNZ].astype(np.int64)
    vals = rs.randn(len(rows), NCOLS).astype(np.float32)
    base = _live_device_bytes()
    g1 = sp.row_sparse_array((vals, rows), shape=(NROWS, NCOLS))
    g2 = sp.row_sparse_array((vals * 2.0, rows), shape=(NROWS, NCOLS))
    diff = sp.elemwise_sub(g1, g2)
    prod = sp.elemwise_mul(g1, g2)
    scaled = g1 * 0.5
    sq = mx.nd.square(g1)
    norms = sp.square_sum(g1, axis=1, keepdims=True)
    jax.block_until_ready(norms._rsp_data)
    grown = _live_device_bytes() - base
    assert grown < dense_bytes // 10, grown
    for a in (g1, g2, diff, prod, scaled, sq, norms):
        assert a._dense_cache is None
    np.testing.assert_allclose(diff.data.asnumpy(), -vals, rtol=1e-6)
    np.testing.assert_allclose(prod.data.asnumpy(), vals * vals * 2.0,
                               rtol=1e-6)
    np.testing.assert_allclose(sq.data.asnumpy(), vals * vals, rtol=1e-6)


def _random_dense(rs, shape, density):
    d = rs.randn(*shape).astype(np.float32)
    mask = rs.rand(*shape) < density
    return d * mask


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_csr_kernels_randomised_midscale(seed):
    """Property check at awkward (non-aligned) shapes: the native csr
    kernel chain against numpy oracles on random 513x257 operands."""
    rs = np.random.RandomState(seed)
    shape = (513, 257)
    a = _random_dense(rs, shape, 0.05)
    b = _random_dense(rs, shape, 0.05)
    ca = sp.cast_storage(mx.nd.array(a), "csr")
    cb = sp.cast_storage(mx.nd.array(b), "csr")

    # structural round trip
    np.testing.assert_allclose(ca.asnumpy(), a, rtol=1e-6)
    assert int(ca.indptr.asnumpy()[-1]) == int((a != 0).sum())

    # csr + csr (native COO-merge path) stays csr and matches numpy
    s = mx.nd.elemwise_add(ca, cb)
    assert s.stype == "csr"
    np.testing.assert_allclose(s.asnumpy(), a + b, rtol=1e-5)
    m = mx.nd.elemwise_mul(ca, cb)
    assert m.stype == "csr"
    np.testing.assert_allclose(m.asnumpy(), a * b, rtol=1e-5)

    # csr . dense and csr^T . dense with gradient through the dense rhs
    w = rs.randn(shape[1], 31).astype(np.float32)
    out = mx.nd.dot(ca, mx.nd.array(w))
    np.testing.assert_allclose(out.asnumpy(), a @ w, rtol=1e-4, atol=1e-4)
    wt = rs.randn(shape[0], 17).astype(np.float32)
    outt = mx.nd.dot(ca, mx.nd.array(wt), transpose_a=True)
    np.testing.assert_allclose(outt.asnumpy(), a.T @ wt, rtol=1e-4,
                               atol=1e-4)

    # sparse<->sparse casts agree with the dense path
    rsp = ca.tostype("row_sparse")
    np.testing.assert_allclose(rsp.asnumpy(), a, rtol=1e-6)
    back = rsp.tostype("csr")
    np.testing.assert_allclose(back.asnumpy(), a, rtol=1e-6)


@pytest.mark.parametrize("seed", [3, 4])
def test_rsp_kernels_randomised_midscale(seed):
    rs = np.random.RandomState(seed)
    nrows, ncols, k = 997, 129, 41
    rows = np.sort(rs.choice(nrows, size=k, replace=False)).astype(np.int64)
    va = rs.randn(k, ncols).astype(np.float32)
    vb = rs.randn(k, ncols).astype(np.float32)
    ga = sp.row_sparse_array((va, rows), shape=(nrows, ncols))
    gb = sp.row_sparse_array((vb, rows), shape=(nrows, ncols))
    dense_a = np.zeros((nrows, ncols), np.float32); dense_a[rows] = va
    dense_b = np.zeros((nrows, ncols), np.float32); dense_b[rows] = vb

    for op, ref in [(mx.nd.elemwise_add, dense_a + dense_b),
                    (mx.nd.elemwise_sub, dense_a - dense_b),
                    (mx.nd.elemwise_mul, dense_a * dense_b)]:
        got = op(ga, gb)
        assert got.stype == "row_sparse"
        np.testing.assert_allclose(got.asnumpy(), ref, rtol=1e-5)

    sq = mx.nd.square(ga)
    assert sq.stype == "row_sparse"
    np.testing.assert_allclose(sq.asnumpy(), dense_a ** 2, rtol=1e-5)
    ssum = sp.square_sum(ga, axis=1, keepdims=True)
    np.testing.assert_allclose(
        ssum.asnumpy(), (dense_a ** 2).sum(axis=1, keepdims=True),
        rtol=1e-4)

    # retain an awkward subset, compare against dense masking
    keep = np.sort(rs.choice(nrows, size=211, replace=False))
    kept = ga.retain(keep)
    dense_keep = np.zeros_like(dense_a)
    dense_keep[keep] = dense_a[keep]
    np.testing.assert_allclose(kept.asnumpy(), dense_keep, rtol=1e-6)
