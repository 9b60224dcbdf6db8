"""Sparse-kernel microbenchmarks (parity: reference
benchmark/python/sparse/{dot.py,cast_storage.py,sparse_op.py} — the
harness the reference ships for its CSR kernels, no published numbers).

Measures the compressed-representation kernels on the attached device at
embedding-scale shapes: dot(csr, dense) fwd, its transpose, rsp<->csr
cast_storage, and csr+csr elemwise_add. Prints one JSON line per case.

    python tools/sparse_bench.py [--rows N] [--cols N] [--density D]
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

# host-side default: pinned to the CPU platform before any backend
# touch, so a run here never takes (or waits for) a chip. Set
# MXTPU_SPARSE_BENCH_TPU=1 on a chip-attached host.
import jax  # noqa: E402

if os.environ.get("MXTPU_SPARSE_BENCH_TPU") != "1":
    jax.config.update("jax_platforms", "cpu")

import numpy as np


def _payload(out):
    """The compressed payload to sync on — NEVER the ._data property,
    which lazily materialises the dense view for sparse arrays."""
    for attr in ("_csr_data", "_rsp_data"):
        o = getattr(out, attr, None)
        if o is not None:
            return o
    return getattr(out, "_data", out)


def bench(fn, iters=10):
    import jax
    jax.block_until_ready(_payload(fn()))  # warm-up
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn()
    jax.block_until_ready(_payload(out))
    return (time.perf_counter() - t0) / iters


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=1_000_000)
    ap.add_argument("--cols", type=int, default=512)
    ap.add_argument("--density", type=float, default=0.00001)
    ap.add_argument("--rhs-cols", type=int, default=64)
    args = ap.parse_args()

    import jax
    import mxnet_tpu as mx
    from mxnet_tpu.ndarray import sparse as sp

    rs = np.random.RandomState(0)
    nnz = max(int(args.rows * args.cols * args.density), 1)
    # unique sorted (row, col) keys: CSR kernels assume no duplicate
    # coordinates
    keys = np.unique(rs.randint(0, args.rows * args.cols, nnz)
                     .astype(np.int64))
    rows, cols = keys // args.cols, keys % args.cols
    nnz = len(keys)
    counts = np.bincount(rows, minlength=args.rows)
    indptr = np.concatenate([[0], np.cumsum(counts)])
    vals = rs.randn(nnz).astype(np.float32)
    csr = sp.CSRNDArray(vals, cols, indptr, (args.rows, args.cols))
    rhs = mx.nd.array(rs.randn(args.cols, args.rhs_cols)
                      .astype(np.float32))
    rhs_t = mx.nd.array(rs.randn(args.rows, args.rhs_cols)
                        .astype(np.float32))

    dev = jax.devices()[0].platform
    base = {"device": dev, "rows": args.rows, "cols": args.cols,
            "nnz": int(nnz)}

    t = bench(lambda: sp.dot(csr, rhs))
    print(json.dumps({**base, "metric": "dot_csr_dense",
                      "value": round(t * 1e3, 3), "unit": "ms",
                      "gflops": round(2 * nnz * args.rhs_cols / t / 1e9,
                                      2)}))
    t = bench(lambda: sp.dot(csr, rhs_t, transpose_a=True))
    print(json.dumps({**base, "metric": "dot_csrT_dense",
                      "value": round(t * 1e3, 3), "unit": "ms"}))
    t = bench(lambda: csr.tostype("row_sparse"))
    print(json.dumps({**base, "metric": "cast_csr_to_rsp",
                      "value": round(t * 1e3, 3), "unit": "ms"}))
    rsp = csr.tostype("row_sparse")
    t = bench(lambda: rsp.tostype("csr"))
    print(json.dumps({**base, "metric": "cast_rsp_to_csr",
                      "value": round(t * 1e3, 3), "unit": "ms"}))
    t = bench(lambda: sp.elemwise_add(csr, csr))
    print(json.dumps({**base, "metric": "elemwise_add_csr_csr",
                      "value": round(t * 1e3, 3), "unit": "ms"}))


if __name__ == "__main__":
    main()
