"""Pallas kernel tests (interpret mode on the CPU mesh; the same kernels
compile natively on TPU — the bench/driver exercises that path)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import parallel
from mxnet_tpu.pallas import flash_attention, flash_attention_carry


def _rand_qkv(seed, B=2, H=3, S=24, D=16):
    rng = np.random.RandomState(seed)
    return [jnp.asarray(rng.normal(size=(B, H, S, D)).astype(np.float32))
            for _ in range(3)]


def test_flash_matches_reference():
    q, k, v = _rand_qkv(0)
    for causal in (False, True):
        ref = parallel.attention(q, k, v, causal=causal)
        got = flash_attention(q, k, v, causal)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)


def test_flash_uneven_seq():
    # S > block_q and not a multiple of it: exercises the real padding
    # path (block_q=8 so S=19 pads to 24) including padded-row gradients
    q, k, v = _rand_qkv(1, S=19)
    ref = parallel.attention(q, k, v, causal=True)
    got = flash_attention(q, k, v, True, None, 8)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)

    def f_ref(q, k, v):
        return jnp.sum(jnp.sin(parallel.attention(q, k, v, causal=True)))

    def f_got(q, k, v):
        return jnp.sum(jnp.sin(flash_attention(q, k, v, True, None, 8)))

    gr = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    gg = jax.grad(f_got, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gr, gg):
        assert np.all(np.isfinite(np.asarray(b)))
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   rtol=1e-4, atol=1e-4)


def test_flash_grads_match_reference():
    q, k, v = _rand_qkv(2, B=1, H=2, S=12, D=8)

    def f_ref(q, k, v):
        return jnp.sum(jnp.sin(parallel.attention(q, k, v, causal=True)))

    def f_got(q, k, v):
        return jnp.sum(jnp.sin(flash_attention(q, k, v, True)))

    gr = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    gg = jax.grad(f_got, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gr, gg):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   rtol=1e-4, atol=1e-4)


def test_carry_chaining_equals_full():
    """Two chained kv blocks with offsets == one full-sequence call — the
    invariant ring attention relies on."""
    B, H, S, D = 1, 2, 16, 8
    q, k, v = _rand_qkv(3, B=B, H=H, S=S, D=D)
    ref = parallel.attention(q, k, v, causal=True)
    qf, kf, vf = [x.reshape(B * H, S, D) for x in (q, k, v)]
    o = jnp.zeros((B * H, S, D), jnp.float32)
    m = jnp.full((B * H, S), -1e30, jnp.float32)
    l = jnp.zeros((B * H, S), jnp.float32)
    half = S // 2
    o, m, l = flash_attention_carry(qf, kf[:, :half], vf[:, :half], o, m, l,
                                    q_offset=0, kv_offset=0, causal=True)
    o, m, l = flash_attention_carry(qf, kf[:, half:], vf[:, half:], o, m, l,
                                    q_offset=0, kv_offset=half, causal=True)
    out = (o / jnp.maximum(l, 1e-30)[..., None]).reshape(B, H, S, D)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_ring_attention_pallas_path():
    """Ring attention with the Pallas local kernel (interpret mode) must
    match the single-chip reference."""
    B, H, S, D = 1, 2, 16, 8
    q, k, v = _rand_qkv(4, B=B, H=H, S=S, D=D)
    mesh = parallel.make_mesh({"sp": 4})
    for causal in (False, True):
        ref = parallel.attention(q, k, v, causal=causal)
        out = parallel.ring_attention(q, k, v, mesh, axis_name="sp",
                                      causal=causal, use_pallas=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-4, atol=2e-5)


def test_ring_attention_pallas_grads():
    """Training through the Pallas ring path: the custom ring VJP must
    match autodiff through the single-chip reference."""
    B, H, S, D = 1, 2, 16, 8
    q, k, v = _rand_qkv(5, B=B, H=H, S=S, D=D)
    mesh = parallel.make_mesh({"sp": 4})

    def f_ref(q, k, v):
        return jnp.sum(jnp.sin(parallel.attention(q, k, v, causal=True)))

    def f_ring(q, k, v):
        out = parallel.ring_attention(q, k, v, mesh, axis_name="sp",
                                      causal=True, use_pallas=True)
        return jnp.sum(jnp.sin(out))

    gr = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    gg = jax.grad(f_ring, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gr, gg):
        assert np.all(np.isfinite(np.asarray(b)))
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   rtol=1e-4, atol=1e-4)


def test_flash_backward_pallas_vs_xla():
    """The Pallas flash backward (dQ over the band of K/V blocks, dK/dV
    over the transposed band, accumulated in scratch) must match plain
    XLA autodiff of the unblocked form on uneven (non-block-multiple)
    sequence lengths, causal and not."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.pallas.flash_attention import flash_attention as fa

    rs = np.random.RandomState(0)
    for causal in (False, True):
        for s_q, s_kv in [(48, 48), (33, 65)] if not causal else [(48, 48)]:
            q = jnp.asarray(rs.randn(1, 2, s_q, 16).astype(np.float32))
            k = jnp.asarray(rs.randn(1, 2, s_kv, 16).astype(np.float32))
            v = jnp.asarray(rs.randn(1, 2, s_kv, 16).astype(np.float32))
            g = jnp.asarray(rs.randn(1, 2, s_q, 16).astype(np.float32))

            def loss(qq, kk, vv):
                return jnp.sum(fa(qq, kk, vv, causal, None, 32) * g)

            def loss_xla(qq, kk, vv):
                return jnp.sum(parallel.attention(qq, kk, vv,
                                                  causal=causal) * g)

            grads_pallas = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
            grads_xla = jax.grad(loss_xla, argnums=(0, 1, 2))(q, k, v)
            for gp, gx, name in zip(grads_pallas, grads_xla,
                                    ("dq", "dk", "dv")):
                np.testing.assert_allclose(
                    np.asarray(gp), np.asarray(gx), rtol=2e-4, atol=2e-4,
                    err_msg="%s causal=%s s=(%d,%d)"
                            % (name, causal, s_q, s_kv))


@pytest.mark.parametrize("window", [0, 96])
def test_flash_named_residuals_are_the_identity(window):
    """``flash_attention`` names its output and log-sum-exp for a mirrored
    segment to keep and hands the log-sum-exp over compact; outside a
    checkpoint that changes no bit: output and gradients equal the two
    kernels called as the rule called them before the names (grouped-query
    heads, an uneven length so the padding is on the path)."""
    import importlib
    fa = importlib.import_module("mxnet_tpu.pallas.flash_attention")

    rs = np.random.RandomState(3)
    q = jnp.asarray(rs.randn(1, 4, 200, 32).astype(np.float32))
    k, v = (jnp.asarray(rs.randn(1, 2, 200, 32).astype(np.float32))
            for _ in range(2))
    g = jnp.asarray(rs.randn(1, 4, 200, 32).astype(np.float32))
    band = fa._plan(q, k, True, window or None, 64, 64)
    scale = 1.0 / np.sqrt(32)

    def pad(x):
        return fa._pad_seq(x, 64)

    out, lse = fa._forward(pad(q), pad(k), pad(v), band, scale, True)
    assert lse.shape == (1, 4, 256, 1)
    out = out[:, :, :200]
    delta = jnp.sum(g * out, -1, keepdims=True)
    want = fa._backward(pad(q), pad(k), pad(v), pad(g), lse, pad(delta),
                        band, scale, True)

    def loss(qq, kk, vv):
        return jnp.sum(flash_attention(qq, kk, vv, True, None, 64, None,
                                       window or None, 64) * g)

    got_out = flash_attention(q, k, v, True, None, 64, None,
                              window or None, 64)
    np.testing.assert_array_equal(np.asarray(got_out), np.asarray(out))
    grads = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(grads, want, ("dq", "dk", "dv")):
        np.testing.assert_array_equal(np.asarray(a),
                                      np.asarray(b[:, :, :200]), name)


def test_scale_bias_add_relu_matches_composed():
    import jax.numpy as jnp
    from mxnet_tpu.pallas.fused_bn import scale_bias_add_relu
    rs = np.random.RandomState(0)
    # shapes chosen to hit: single block (105x33), a PARTIAL row block
    # (280 rows > BLOCK_ROWS=256, not a multiple), and a partial column
    # block (600 cols > BLOCK_COLS=512)
    for shape in ((3, 5, 7, 33), (2, 20, 7, 33), (2, 2, 2, 600)):
        c = shape[-1]
        x = jnp.asarray(rs.randn(*shape).astype(np.float32))
        r = jnp.asarray(rs.randn(*shape).astype(np.float32))
        s = jnp.asarray(rs.randn(c).astype(np.float32))
        b = jnp.asarray(rs.randn(c).astype(np.float32))
        got = scale_bias_add_relu(x, s, b, r)
        want = np.maximum(np.asarray(x) * np.asarray(s) + np.asarray(b)
                          + np.asarray(r), 0.0)
        np.testing.assert_allclose(np.asarray(got), want, rtol=1e-6,
                                   atol=1e-6)
        # no-residual form
        got2 = scale_bias_add_relu(x, s, b)
        want2 = np.maximum(np.asarray(x) * np.asarray(s) + np.asarray(b),
                           0.0)
        np.testing.assert_allclose(np.asarray(got2), want2, rtol=1e-6,
                                   atol=1e-6)


def test_scale_bias_add_relu_bf16():
    import jax.numpy as jnp
    from mxnet_tpu.pallas.fused_bn import scale_bias_add_relu
    rs = np.random.RandomState(1)
    x = jnp.asarray(rs.randn(4, 8, 8, 16)).astype(jnp.bfloat16)
    r = jnp.asarray(rs.randn(4, 8, 8, 16)).astype(jnp.bfloat16)
    s = jnp.asarray(rs.randn(16).astype(np.float32))
    b = jnp.asarray(rs.randn(16).astype(np.float32))
    got = scale_bias_add_relu(x, s, b, r)
    assert got.dtype == jnp.bfloat16
    want = np.maximum(
        np.asarray(x, np.float32) * np.asarray(s.astype(jnp.bfloat16),
                                               np.float32)
        + np.asarray(b.astype(jnp.bfloat16), np.float32)
        + np.asarray(r, np.float32), 0.0)
    np.testing.assert_allclose(np.asarray(got, np.float32), want,
                               rtol=2e-2, atol=2e-2)


def test_scale_bias_add_relu_grad():
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.pallas.fused_bn import scale_bias_add_relu
    rs = np.random.RandomState(2)
    x = jnp.asarray(rs.randn(2, 3, 3, 9).astype(np.float32))
    r = jnp.asarray(rs.randn(2, 3, 3, 9).astype(np.float32))
    s = jnp.asarray(rs.rand(9).astype(np.float32) + 0.5)
    b = jnp.asarray(rs.randn(9).astype(np.float32))

    def fused(x, s, b, r):
        return jnp.sum(scale_bias_add_relu(x, s, b, r) ** 2)

    def composed(x, s, b, r):
        return jnp.sum(jnp.maximum(x * s + b + r, 0.0) ** 2)

    g1 = jax.grad(fused, argnums=(0, 1, 2, 3))(x, s, b, r)
    g2 = jax.grad(composed, argnums=(0, 1, 2, 3))(x, s, b, r)
    for a, e in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(e), rtol=1e-5,
                                   atol=1e-5)


def test_batch_norm_add_relu_op_matches_bn_chain():
    """_contrib_BatchNormAddReLU == BatchNorm -> +residual -> relu in
    both training and inference mode, channels-last AND channels-first,
    including the moving-stat writeback."""
    rs = np.random.RandomState(3)
    for axis, shape in ((3, (2, 4, 4, 6)), (1, (2, 6, 4, 4))):
        c = shape[axis]
        x = mx.nd.array(rs.randn(*shape).astype(np.float32))
        res = mx.nd.array(rs.randn(*shape).astype(np.float32))
        gamma = mx.nd.array(rs.rand(c).astype(np.float32) + 0.5)
        beta = mx.nd.array(rs.randn(c).astype(np.float32))

        for train in (True, False):
            mean1 = mx.nd.zeros((c,))
            var1 = mx.nd.ones((c,))
            mean2 = mx.nd.zeros((c,))
            var2 = mx.nd.ones((c,))
            from mxnet_tpu import autograd
            with autograd.record(train_mode=train):
                bn = mx.nd.BatchNorm(x, gamma, beta, mean1, var1,
                                     fix_gamma=False, axis=axis)
                want = mx.nd.relu(bn + res)
                got = mx.nd._contrib_BatchNormAddReLU(
                    x, res, gamma, beta, mean2, var2, fix_gamma=False,
                    axis=axis)
            np.testing.assert_allclose(got.asnumpy(), want.asnumpy(),
                                       rtol=1e-5, atol=1e-5)
            # moving stats updated identically
            np.testing.assert_allclose(mean2.asnumpy(), mean1.asnumpy(),
                                       rtol=1e-6)
            np.testing.assert_allclose(var2.asnumpy(), var1.asnumpy(),
                                       rtol=1e-6)


def test_batch_norm_add_relu_symbol_bind():
    """The fused op composes and trains through the symbolic executor."""
    rs = np.random.RandomState(4)
    data = mx.sym.Variable("data")
    res = mx.sym.Variable("res")
    out = mx.sym._contrib_BatchNormAddReLU(data, res, name="bnar",
                                           fix_gamma=False, axis=3)
    out = mx.sym.sum(out)
    ex = out.simple_bind(ctx=mx.cpu(), data=(2, 3, 3, 5), res=(2, 3, 3, 5))
    ex.arg_dict["data"][:] = rs.randn(2, 3, 3, 5).astype(np.float32)
    ex.arg_dict["res"][:] = rs.randn(2, 3, 3, 5).astype(np.float32)
    ex.arg_dict["bnar_gamma"][:] = 1.0
    ex.arg_dict["bnar_beta"][:] = 0.0
    ex.forward(is_train=True)
    ex.backward()
    g = ex.grad_dict["data"].asnumpy()
    assert np.isfinite(g).all() and (g != 0).any()
