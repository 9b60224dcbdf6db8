"""Distributed/sharding tests on the virtual 8-device CPU mesh
(parity model: reference tests/nightly/dist_sync_kvstore.py run via
launch.py local mode — multi-device semantics without a cluster)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import parallel


def test_mesh_creation():
    mesh = parallel.make_mesh({"dp": 4, "tp": 2})
    assert mesh.shape["dp"] == 4 and mesh.shape["tp"] == 2
    mesh2 = parallel.make_mesh({"dp": -1})
    assert mesh2.shape["dp"] == 8


def test_ring_attention_matches_reference():
    np.random.seed(0)
    B, H, S, D = 2, 4, 16, 8
    q = np.random.normal(size=(B, H, S, D)).astype(np.float32)
    k = np.random.normal(size=(B, H, S, D)).astype(np.float32)
    v = np.random.normal(size=(B, H, S, D)).astype(np.float32)
    mesh = parallel.make_mesh({"sp": 4})
    ref = parallel.attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v))
    out = parallel.ring_attention(jnp.asarray(q), jnp.asarray(k),
                                  jnp.asarray(v), mesh, axis_name="sp")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4,
                               atol=2e-5)


def test_ring_attention_causal():
    np.random.seed(1)
    B, H, S, D = 1, 2, 8, 4
    q = np.random.normal(size=(B, H, S, D)).astype(np.float32)
    k = np.random.normal(size=(B, H, S, D)).astype(np.float32)
    v = np.random.normal(size=(B, H, S, D)).astype(np.float32)
    mesh = parallel.make_mesh({"sp": 4})
    ref = parallel.attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                             causal=True)
    out = parallel.ring_attention(jnp.asarray(q), jnp.asarray(k),
                                  jnp.asarray(v), mesh, axis_name="sp",
                                  causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4,
                               atol=2e-5)


def test_spmd_trainer_dp():
    """Sharded dp training must match single-device numerics."""
    np.random.seed(0)
    W = np.random.normal(0, 0.1, (4, 8)).astype(np.float32)
    b = np.zeros((4,), np.float32)
    X = np.random.normal(size=(16, 8)).astype(np.float32)
    Y = np.random.randint(0, 4, 16).astype(np.int32)

    def apply_fn(params, x, y):
        logits = x @ params["w"].T + params["b"]
        logp = jax.nn.log_softmax(logits)
        return -jnp.mean(jnp.take_along_axis(logp, y[:, None], axis=1))

    mesh = parallel.make_mesh({"dp": 8})
    tr = parallel.SPMDTrainer(apply_fn, {"w": W.copy(), "b": b.copy()}, mesh,
                              data_axis="dp", learning_rate=0.1)
    losses = [float(tr.step(X, Y)) for _ in range(3)]
    assert losses[2] < losses[0]

    # single-device reference
    params = {"w": jnp.asarray(W), "b": jnp.asarray(b)}
    for _ in range(3):
        loss, grads = jax.value_and_grad(apply_fn)(params, jnp.asarray(X),
                                                   jnp.asarray(Y))
        params = {k: params[k] - 0.1 * grads[k] for k in params}
    got = tr.get_params()
    np.testing.assert_allclose(got["w"], np.asarray(params["w"]), rtol=1e-4,
                               atol=1e-5)


def test_spmd_trainer_dp_tp():
    np.random.seed(0)
    W1 = np.random.normal(0, 0.1, (16, 8)).astype(np.float32)
    W2 = np.random.normal(0, 0.1, (4, 16)).astype(np.float32)
    X = np.random.normal(size=(8, 8)).astype(np.float32)
    Y = np.random.randint(0, 4, 8).astype(np.int32)

    def apply_fn(params, x, y):
        h = jnp.maximum(x @ params["w1"].T, 0)
        logits = h @ params["w2"].T
        logp = jax.nn.log_softmax(logits)
        return -jnp.mean(jnp.take_along_axis(logp, y[:, None], axis=1))

    mesh = parallel.make_mesh({"dp": 4, "tp": 2})
    tr = parallel.SPMDTrainer(apply_fn, {"w1": W1, "w2": W2}, mesh,
                              data_axis="dp", tp_axis="tp",
                              learning_rate=0.1, momentum=0.9)
    l0 = float(tr.step(X, Y))
    l1 = float(tr.step(X, Y))
    l2 = float(tr.step(X, Y))
    assert l2 < l0


def test_collectives_shard_map():
    # parallel.shard_map is the version shim: jax.shard_map where the
    # installed JAX has it, the jax.experimental implementation otherwise
    mesh = parallel.make_mesh({"dp": 8})
    x = jnp.arange(8.0)

    def f(v):
        return parallel.all_reduce(v, "dp")

    out = parallel.shard_map(f, mesh=mesh,
                             in_specs=jax.sharding.PartitionSpec("dp"),
                             out_specs=jax.sharding.PartitionSpec("dp"))(x)
    np.testing.assert_allclose(np.asarray(out), np.full(8, 28.0))


def test_kvstore_multi_device_push_pull():
    """The single-process multi-'device' kvstore semantics test
    (parity: tests/nightly/test_kvstore.py)."""
    from mxnet_tpu import nd
    kv = mx.kvstore.create("device")
    kv.init(3, nd.ones((2, 3)))
    grads = [nd.ones((2, 3)) * (i + 1) for i in range(4)]
    kv.push(3, grads)
    out = nd.zeros((2, 3))
    kv.pull(3, out=out)
    np.testing.assert_allclose(out.asnumpy(), np.full((2, 3), 10.0))


def test_ulysses_attention_matches_reference():
    np.random.seed(2)
    B, H, S, D = 2, 8, 16, 4  # H divisible by sp=4
    q = np.random.normal(size=(B, H, S, D)).astype(np.float32)
    k = np.random.normal(size=(B, H, S, D)).astype(np.float32)
    v = np.random.normal(size=(B, H, S, D)).astype(np.float32)
    mesh = parallel.make_mesh({"sp": 4})
    ref = parallel.attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v))
    out = parallel.ulysses_attention(jnp.asarray(q), jnp.asarray(k),
                                     jnp.asarray(v), mesh, axis_name="sp")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4,
                               atol=2e-5)


def test_ulysses_attention_causal():
    np.random.seed(3)
    B, H, S, D = 1, 4, 16, 4
    q = np.random.normal(size=(B, H, S, D)).astype(np.float32)
    k = np.random.normal(size=(B, H, S, D)).astype(np.float32)
    v = np.random.normal(size=(B, H, S, D)).astype(np.float32)
    mesh = parallel.make_mesh({"sp": 4})
    ref = parallel.attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                             causal=True)
    out = parallel.ulysses_attention(jnp.asarray(q), jnp.asarray(k),
                                     jnp.asarray(v), mesh, axis_name="sp",
                                     causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4,
                               atol=2e-5)


def test_ulysses_rejects_uneven_heads():
    import pytest
    mesh = parallel.make_mesh({"sp": 4})
    q = jnp.zeros((1, 3, 16, 4))  # 3 heads not divisible by 4
    with pytest.raises(Exception, match="divisible"):
        parallel.ulysses_attention(q, q, q, mesh, axis_name="sp")


def test_ulysses_differentiable():
    np.random.seed(4)
    B, H, S, D = 1, 4, 16, 4
    q = jnp.asarray(np.random.normal(size=(B, H, S, D)).astype(np.float32))
    mesh = parallel.make_mesh({"sp": 4})

    def loss(q, k, v):
        return parallel.ulysses_attention(q, k, v, mesh,
                                          axis_name="sp").sum()

    g = jax.grad(loss)(q, q, q)
    assert g.shape == q.shape
    assert np.isfinite(np.asarray(g)).all()


def test_spmd_trainer_adam_matches_eager():
    """dp/tp Adam in the sharded step must match the eager mx.optimizer
    Adam applied to the same grads."""
    import mxnet_tpu as mx
    from mxnet_tpu import nd

    np.random.seed(0)
    W = np.random.normal(0, 0.1, (8, 8)).astype(np.float32)
    X = np.random.normal(size=(16, 8)).astype(np.float32)
    Y = np.random.randint(0, 8, 16).astype(np.int32)

    def apply_fn(params, x, y):
        logits = x @ params["w"].T
        logp = jax.nn.log_softmax(logits)
        return -jnp.mean(jnp.take_along_axis(logp, y[:, None], axis=1))

    mesh = parallel.make_mesh({"dp": 4, "tp": 2})
    opt = mx.optimizer.Adam(learning_rate=0.05)
    tr = parallel.SPMDTrainer(apply_fn, {"w": W.copy()}, mesh,
                              data_axis="dp", tp_axis="tp", optimizer=opt)
    for _ in range(3):
        tr.step(X, Y)

    # eager reference: same grads through mx.optimizer.Adam
    eager_opt = mx.optimizer.Adam(learning_rate=0.05)
    weight = nd.array(W.copy())
    state = eager_opt.create_state(0, weight)
    params = {"w": jnp.asarray(W)}
    for _ in range(3):
        _, grads = jax.value_and_grad(apply_fn)(params, jnp.asarray(X),
                                                jnp.asarray(Y))
        eager_opt.update(0, weight, nd.array(np.asarray(grads["w"])), state)
        params = {"w": weight._data}
    np.testing.assert_allclose(tr.get_params()["w"], weight.asnumpy(),
                               rtol=1e-4, atol=1e-5)


def test_spmd_trainer_rmsprop_and_adagrad_run():
    np.random.seed(0)
    W = np.random.normal(0, 0.1, (4, 8)).astype(np.float32)
    X = np.random.normal(size=(8, 8)).astype(np.float32)
    Y = np.random.randint(0, 4, 8).astype(np.int32)

    def apply_fn(params, x, y):
        logits = x @ params["w"].T
        logp = jax.nn.log_softmax(logits)
        return -jnp.mean(jnp.take_along_axis(logp, y[:, None], axis=1))

    mesh = parallel.make_mesh({"dp": 8})
    for name, kw in [("rmsprop", {"gamma1": 0.9, "epsilon": 1e-8}),
                     ("adagrad", {"eps": 1e-7}),
                     ("adagrad", {}),          # registry defaults path
                     ("nag", {"momentum": 0.9})]:
        tr = parallel.SPMDTrainer(apply_fn, {"w": W.copy()}, mesh,
                                  data_axis="dp", optimizer=name,
                                  learning_rate=0.05, **kw)
        l0 = float(tr.step(X, Y))
        l1 = float(tr.step(X, Y))
        l2 = float(tr.step(X, Y))
        assert np.isfinite(l1) and np.isfinite(l2) and l2 < l0, \
            (name, l0, l1, l2)


def _switch_oracle(flat, wr, w1, w2):
    """Dense per-token oracle of a top-1 softmax-gated ReLU expert layer;
    w1 (n, E, F), w2 (n, F, E)."""
    logits = flat @ wr.T
    probs = np.exp(logits - logits.max(1, keepdims=True))
    probs /= probs.sum(1, keepdims=True)
    exp = probs.argmax(1)
    gate = probs[np.arange(len(flat)), exp]
    want = np.zeros_like(flat)
    for i, (tok, e) in enumerate(zip(flat, exp)):
        want[i] = (np.maximum(tok @ w1[e], 0) @ w2[e]) * gate[i]
    return want, exp


def test_moe_ffn_matches_dense_oracle():
    """The held-experts layer as a top-1 softmax switch layer (no gate,
    ReLU, weights not normalised) must equal the dense per-token oracle,
    and the shares of an ``ep`` axis (here 4 devices' worth, one expert
    each) must add up to it."""
    import jax.numpy as jnp
    from mxnet_tpu.parallel import moe

    n = 4
    rs = np.random.RandomState(0)
    B, T, E, F = 4, 8, 16, 32
    x = rs.randn(B, T, E).astype(np.float32) * 0.5
    wr = rs.randn(n, E).astype(np.float32)
    w1 = rs.randn(n, E, F).astype(np.float32) * 0.1
    w2 = rs.randn(n, F, E).astype(np.float32) * 0.1
    want, _ = _switch_oracle(x.reshape(-1, E), wr, w1, w2)

    def layer(first, count):
        out, counts = moe.moe_layer(
            jnp.asarray(x), jnp.asarray(wr), None,
            jnp.asarray(w1[first:first + count]), None,
            jnp.asarray(w2[first:first + count]), top_k=1,
            experts_held=(first, count), score_func="softmax",
            route_norm=False, act="relu")
        assert int(counts.sum()) == B * T
        return np.asarray(out).reshape(-1, E)

    np.testing.assert_allclose(layer(0, n), want, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(sum(layer(e, 1) for e in range(n)), want,
                               rtol=1e-4, atol=1e-4)


def test_moe_capacity_drops_tokens():
    """The layer is dropless: with a router that sends EVERY token to
    one held expert (the case in which the old switch layer's capacity
    zeroed the overflow), every token still gets its expert's output."""
    import jax.numpy as jnp
    from mxnet_tpu.parallel import moe

    n = 2
    rs = np.random.RandomState(1)
    B, T, E, F = 2, 8, 8, 8
    x = np.abs(rs.randn(B, T, E)).astype(np.float32)
    wr = np.zeros((n, E), np.float32)
    wr[0] = 1e3
    w1 = np.ones((n, E, F), np.float32) * 0.01
    w2 = np.ones((n, F, E), np.float32) * 0.01
    out, counts = moe.moe_layer(
        jnp.asarray(x), jnp.asarray(wr), None, jnp.asarray(w1), None,
        jnp.asarray(w2), top_k=1, score_func="softmax", route_norm=False,
        act="relu")
    assert list(np.asarray(counts)) == [B * T, 0]
    want, exp = _switch_oracle(x.reshape(-1, E), wr, w1, w2)
    assert (exp == 0).all()
    flat = np.asarray(out).reshape(-1, E)
    assert (np.abs(flat).sum(1) > 0).all()
    np.testing.assert_allclose(flat, want, rtol=1e-5, atol=1e-6)


def test_pipeline_matches_sequential():
    """GPipe pipeline over the 'pp' axis equals applying the stages in
    sequence; gradients flow through the scan/ppermute schedule."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu import parallel

    n = 4
    mesh = parallel.make_mesh({"pp": n})
    rs = np.random.RandomState(2)
    E = 8
    n_micro = 6
    x = rs.randn(n_micro, 3, E).astype(np.float32)
    w = rs.randn(n, E, E).astype(np.float32) * 0.3
    b = rs.randn(n, E).astype(np.float32) * 0.1

    def stage(params, mb):
        return jnp.tanh(mb @ params["w"] + params["b"])

    got = np.asarray(parallel.pipeline_apply(
        stage, {"w": jnp.asarray(w), "b": jnp.asarray(b)},
        jnp.asarray(x), mesh, axis_name="pp"))

    want = x.copy()
    for s in range(n):
        want = np.tanh(want @ w[s] + b[s])
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)

    # differentiable end to end
    def loss(ws):
        out = parallel.pipeline_apply(
            stage, {"w": ws, "b": jnp.asarray(b)}, jnp.asarray(x), mesh)
        return jnp.sum(out ** 2)

    g = jax.grad(loss)(jnp.asarray(w))
    assert np.isfinite(np.asarray(g)).all() and np.abs(g).sum() > 0
