"""The decoder ops (``ops/lm.py``), the tiled attention kernel, the
held-experts layer and the ``afmoe`` symbol, each against the plain
reference ``benchmarks/reference/afmoe.py`` (float32, small sizes, seeded
random weights)."""
import importlib.util
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu.ops import lm, get_op
from mxnet_tpu.parallel import moe
from mxnet_tpu.pallas.flash_attention import flash_attention

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(path, name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(ROOT, path))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load("benchmarks/reference/afmoe.py", "afmoe_reference")
afmoe = _load("examples/language-model/symbols/afmoe.py", "afmoe_symbol")

CONFIG = dict(
    hidden_size=32, num_attention_heads=4, num_key_value_heads=2,
    head_dim=8, layer_types=["sliding_attention"] * 3 + ["full_attention"],
    num_hidden_layers=4, sliding_window=6, rope_theta=10000,
    num_dense_layers=1, intermediate_size=48, num_experts=8,
    num_experts_per_tok=2, moe_intermediate_size=16, num_shared_experts=1,
    score_func="sigmoid", route_norm=True, route_scale=2.826,
    load_balance_coeff=0.001, rms_norm_eps=1e-5, vocab_size=40,
    mup_enabled=True, experts_held=[2, 4])


def _rand(rs, *shape, scale=1.0):
    return jnp.asarray(rs.randn(*shape).astype(np.float32) * scale)


def _close(a, b, tol=2e-5):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=tol,
                               atol=tol)


def _check(op_fn, ref_fn, args, tol=2e-5):
    """Forward and the gradient of a fixed projection of the output."""
    out, want = op_fn(*args), ref_fn(*args)
    _close(out, want, tol)
    w = jnp.cos(jnp.arange(want.size, dtype=jnp.float32)).reshape(want.shape)
    nums = tuple(range(len(args)))
    got = jax.grad(lambda *a: jnp.sum(op_fn(*a) * w), nums)(*args)
    exp = jax.grad(lambda *a: jnp.sum(ref_fn(*a) * w), nums)(*args)
    for g, e in zip(got, exp):
        _close(g, e, tol)


# -- each op against the reference's function --------------------------------

@pytest.mark.parametrize("group", [0, 8])
def test_rms_norm(group):
    rs = np.random.RandomState(0)
    x, g = _rand(rs, 2, 5, 32), 1 + _rand(rs, group or 32, scale=0.1)

    def want(x, g):
        y = x.reshape(2, 5, -1, group) if group else x
        return ref.rms(y, g, 1e-5).reshape(x.shape)

    _check(lambda x, g: lm.rms_norm(x, g, 1e-5, group), want, (x, g))


def test_rotary_embedding():
    rs = np.random.RandomState(1)
    x = _rand(rs, 2, 7, 32)
    _check(lambda x: lm.rotary_embedding(x, 8, 10000.0),
           lambda x: jnp.stack([ref.rope(s.reshape(7, 4, 8), 10000.0)
                                .reshape(7, 32) for s in x]), (x,))


def test_silu_gate():
    rs = np.random.RandomState(2)
    _check(lm.silu_gate, lambda a, b: jax.nn.silu(a) * b,
           (_rand(rs, 3, 9), _rand(rs, 3, 9)))


@pytest.mark.parametrize("window", [0, 5])
@pytest.mark.parametrize("kv_heads", [4, 2, 1])
def test_causal_attention(window, kv_heads):
    rs = np.random.RandomState(3)
    t, hq, d = 19, 4, 8
    q, k, v = (_rand(rs, 2, t, hq * d), _rand(rs, 2, t, kv_heads * d),
               _rand(rs, 2, t, kv_heads * d))

    def want(q, k, v):
        return jnp.stack([ref.attention(
            a.reshape(t, hq, d), b.reshape(t, kv_heads, d),
            c.reshape(t, kv_heads, d), window) for a, b, c in zip(q, k, v)])

    _check(lambda q, k, v: lm.causal_attention(q, k, v, hq, kv_heads,
                                               window),
           want, (q, k, v), tol=1e-4)


def _moe_params(rs, c, d, f, held=None):
    n = c["num_experts"]
    count = held[1] if held else n
    return dict(router=_rand(rs, n, d, scale=0.5),
                w1=_rand(rs, count, d, f, scale=0.2),
                w3=_rand(rs, count, d, f, scale=0.2),
                w2=_rand(rs, count, f, d, scale=0.2))


def _ref_moe(x, p, bias, c):
    named = {"m_router_weight": p["router"], "m_expert_w1_weight": p["w1"],
             "m_expert_w3_weight": p["w3"], "m_expert_w2_weight": p["w2"]}
    return ref.routed(x, named, bias, "m", c, None)


def test_moe_op():
    rs = np.random.RandomState(4)
    c, d, f = CONFIG, 32, 16
    p = _moe_params(rs, c, d, f, c["experts_held"])
    x, bias = _rand(rs, 24, d), _rand(rs, 8, scale=0.05)
    kw = dict(num_experts=8, top_k=2, hidden=f,
              experts_held=tuple(c["experts_held"]), route_scale=2.826)

    def got(x, router, w1, w3, w2):
        return lm.moe(x, router, w1, w3, w2, bias, jnp.zeros(5), **kw)[0]

    def want(x, router, w1, w3, w2):
        return _ref_moe(x, dict(router=router, w1=w1, w3=w3, w2=w2), bias,
                        c)[0]

    _check(got, want, (x, p["router"], p["w1"], p["w3"], p["w2"]), tol=1e-4)
    counts = lm.moe(x, p["router"], p["w1"], p["w3"], p["w2"], bias,
                    jnp.zeros(5), **kw)[1]
    _close(counts, _ref_moe(x, p, bias, c)[1])
    # the state a training step writes: the bias by the rule, the load
    new = get_op("_contrib_MoE").stateful_update(
        [x, p["router"], p["w1"], p["w3"], p["w2"], bias, jnp.zeros(5)],
        (None, counts), dict(kw, _train=True, load_balance_coeff=0.001))
    _close(new[5], ref.bias_update(bias, counts.astype(jnp.float32), 0.001))
    rows = np.asarray(counts)[2:6]
    _close(new[6], [1, rows.sum(), rows.max(), 1, 0])


def test_token_cross_entropy():
    rs = np.random.RandomState(5)
    x, w = _rand(rs, 2, 8, 16), _rand(rs, 40, 16, scale=0.3)
    y = jnp.asarray(rs.randint(0, 40, (2, 8)), jnp.float32)
    for block in (2048, 4):     # whole, and a block of positions at a time
        _check(lambda x, w: lm.token_cross_entropy(x, w, y, 40, block),
               lambda x, w: ref.head_loss(x.reshape(16, 16), w,
                                          y.reshape(16).astype(jnp.int32),
                                          None).reshape(2, 8), (x, w))


# -- the kernel in interpret mode against the plain form ---------------------

@pytest.mark.parametrize("case", [
    # hq, hkv, s_q, s_kv, causal, window, block_q, block_k
    (4, 4, 24, 24, False, None, 8, 8), (4, 2, 40, 40, True, None, 8, 16),
    (8, 2, 64, 64, True, 20, 16, 8), (4, 1, 37, 37, True, 9, 8, 8),
    (2, 2, 33, 65, False, None, 32, 32), (2, 1, 48, 48, True, 48, 16, 16),
    (2, 2, 19, 19, True, 1, 8, None),
    # ..., keys' width, values' width: latent attention's 192 / 128 in
    # small, S no multiple of the block
    (4, 4, 37, 37, True, None, 8, 8, 24, 16),
    (4, 2, 40, 40, True, None, 8, 16, 24, 16),
    (2, 2, 33, 33, True, None, 32, None, 16, 24),
    (4, 1, 37, 37, True, 9, 8, 8, 24, 16),
    (2, 2, 33, 65, False, None, 32, 32, 12, 8)],
    ids=lambda c: "hq%d_hkv%d_s%dx%d_c%d_w%s_b%sx%s" % c[:8]
    + ("_d%dx%d" % c[8:] if c[8:] else ""))
def test_flash_kernel_matches_plain(case):
    hq, hkv, s_q, s_kv, causal, window, bq, bk = case[:8]
    d, dv = case[8:] or (16, 16)
    rs = np.random.RandomState(6)
    q, k, v = (_rand(rs, 2, hq, s_q, d), _rand(rs, 2, hkv, s_kv, d),
               _rand(rs, 2, hkv, s_kv, dv))

    def plain(q, k, v):
        g = hq // hkv
        kk, vv = jnp.repeat(k, g, axis=1), jnp.repeat(v, g, axis=1)
        s = jnp.einsum("bhqd,bhkd->bhqk", q, kk) / np.sqrt(d)
        i, j = jnp.arange(s_q)[:, None], jnp.arange(s_kv)[None]
        m = jnp.ones((s_q, s_kv), bool)
        if causal:
            m = m & (i >= j)
        if window:
            m = m & (i - j < window)
        return jnp.einsum("bhqk,bhkd->bhqd",
                          jax.nn.softmax(jnp.where(m, s, -1e30), -1), vv)

    _check(lambda q, k, v: flash_attention(q, k, v, causal, None, bq, None,
                                           window, bk), plain, (q, k, v))


# -- the held experts --------------------------------------------------------

def test_shares_add_up_to_the_whole_layer():
    """The eight shares' routed parts (each chip's two held experts) plus
    the shared expert counted once give the uncut reference's layer."""
    rs = np.random.RandomState(7)
    c = dict(CONFIG, experts_held=None)
    d, f = 32, 16
    p = _moe_params(rs, c, d, f)
    x, bias = _rand(rs, 30, d), _rand(rs, 8, scale=0.05)
    shared = {k: _rand(rs, *s, scale=0.2) for k, s in
              (("s_w1_weight", (f, d)), ("s_w3_weight", (f, d)),
               ("s_w2_weight", (d, f)))}
    whole = ref.gated(x, shared, "s", None) + _ref_moe(x, p, bias, c)[0]
    total = lm.silu_gate(x @ shared["s_w1_weight"].T,
                         x @ shared["s_w3_weight"].T) @ shared["s_w2_weight"].T
    for first in range(0, 8, 1):
        part, counts = moe.moe_layer(
            x, p["router"], bias, p["w1"][first:first + 1],
            p["w3"][first:first + 1], p["w2"][first:first + 1], top_k=2,
            experts_held=(first, 1), route_scale=2.826)
        assert int(counts.sum()) == 30 * 2      # every share routes alike
        total = total + part
    _close(total, whole, tol=1e-4)


def test_dropless_under_forced_imbalance():
    """All tokens to one held expert: it takes every row, none is lost."""
    rs = np.random.RandomState(8)
    c = dict(CONFIG, num_experts_per_tok=1, experts_held=[0, 2],
             num_experts=4)
    p = _moe_params(rs, c, 32, 16, (0, 2))
    p["router"] = jnp.zeros((4, 32)).at[1].set(50.0)
    x = jnp.abs(_rand(rs, 64, 32))
    out, counts = moe.moe_layer(x, p["router"], jnp.zeros(4), p["w1"],
                                p["w3"], p["w2"], top_k=1,
                                experts_held=(0, 2), route_scale=2.826)
    assert list(np.asarray(counts)) == [0, 64, 0, 0]
    _close(out, _ref_moe(x, p, jnp.zeros(4), c)[0], tol=1e-4)
    assert (np.abs(np.asarray(out)).sum(1) > 0).all()


# -- the chunks of the sorted order ------------------------------------------

def _plain_whole_buffer(x, sel, w, counts, w1, w3, w2, first=0):
    """The held experts' sum as the layer made it before it had chunks:
    every pass over all ``T k`` sorted rows, the rows past the held ones
    masked on the way in and out, each grouped product in float32 and
    rounded to x's type. What the chunked layer must equal bit for bit."""
    t, k = sel.shape
    count = w1.shape[0]
    local = sel.reshape(-1) - first
    key = jnp.where((local >= 0) & (local < count), local, count)
    order = jnp.argsort(key, stable=True)
    inv = jnp.zeros_like(order).at[order].set(jnp.arange(t * k))
    rows = jnp.asarray(counts)[first:first + count]
    real = (jnp.arange(t * k) < jnp.sum(rows))[:, None]

    def dot(a, b):
        return jax.lax.ragged_dot(
            a, b, rows, preferred_element_type=jnp.float32).astype(x.dtype)

    @jax.custom_vjp
    def spread(x):              # pulled back as the combine runs forward
        return x[order // k]

    def collect(y):
        return jnp.sum(y[inv].reshape(t, k, -1).astype(jnp.float32),
                       axis=1).astype(y.dtype)

    spread.defvjp(lambda x: (spread(x), None), lambda _, g: (collect(g),))
    xs = jnp.where(real, spread(x), 0)
    y = dot(jax.nn.silu(dot(xs, w1)) * dot(xs, w3), w2)
    y = jnp.where(real, y.astype(jnp.float32)
                  * w.reshape(-1)[order][:, None], 0).astype(x.dtype)
    return collect(y)


#: 8 of 64 experts held, T 512, k 8: the even share of the 4,096
#: selections is 512 rows, one tile, so the sorted order has 8 chunks.
#: ``lift`` is the held experts' selection bias (it moves the top-k and
#: not the weights): it sets how many selections land here. The last
#: case holds all of its 8 experts: one chunk of T k rows.
_CHUNK_CASES = [
    # id, experts, held, T, k, lift, held rows, chunks they fill
    ("no_row_held", 64, (8, 8), 512, 8, -2.0, 0, 0),
    ("half_a_chunk", 64, (8, 8), 512, 8, -0.05, 258, 1),
    ("one_chunk_nearly_full", 64, (8, 8), 512, 8, 0.0, 504, 1),
    ("group_split_at_the_boundary", 64, (8, 8), 512, 8, 0.05, 729, 2),
    ("two_chunks", 64, (8, 8), 512, 8, 0.1, 933, 2),
    ("three_chunks", 64, (8, 8), 512, 8, 0.2, 1301, 3),
    ("four_chunks", 64, (8, 8), 512, 8, 0.4, 2001, 4),
    ("every_selection_here", 64, (8, 8), 512, 8, 2.0, 4096, 8),
    ("all_experts_held", 8, (0, 8), 64, 2, 0.0, 128, 1),
]


@pytest.mark.parametrize("case", _CHUNK_CASES, ids=lambda c: c[0])
def test_chunked_layer_matches_reference(case):
    """Forward and the gradients of ``x``, the router and the three
    expert stacks against the dense reference, at every load: no token is
    dropped however many chunks the held rows fill."""
    _, n, held, t, k, lift, want_rows, want_chunks = case
    first, count = held
    rs = np.random.RandomState(11)
    d, f = 16, 8
    c = dict(CONFIG, num_experts=n, num_experts_per_tok=k,
             experts_held=list(held))
    x = _rand(rs, t, d)
    router = _rand(rs, n, d, scale=0.5)
    bias = jnp.zeros(n).at[first:first + count].set(lift)
    p = _moe_params(rs, c, d, f, held)

    size = moe.chunk_rows(t * k, count, n)
    assert size == (512 if count < n else t * k)
    counts = np.asarray(moe.moe_layer(
        x, router, bias, p["w1"], p["w3"], p["w2"], k, held,
        route_scale=2.826)[1])[first:first + count]
    assert counts.sum() == want_rows
    assert -(-int(counts.sum()) // size) == want_chunks
    # the row passes run whole stairs of three chunks: those that the held
    # rows and a tile of noughts after them reach into
    chunks, overflow = moe.chunk_load(jnp.asarray(counts), t * k, n)
    assert int(chunks) == min(3 * -(-(want_rows + 512) // (3 * size)),
                              t * k // size)
    assert int(chunks) * size >= min(want_rows + 512, t * k)
    assert int(overflow) == max(want_rows - size, 0)
    if case[0] == "group_split_at_the_boundary":
        ends = np.cumsum(counts)
        assert ((ends - counts < size) & (ends > size)).any()

    def got(x, router, w1, w3, w2):
        return moe.moe_layer(x, router, bias, w1, w3, w2, k, held,
                             route_scale=2.826)[0]

    def want(x, router, w1, w3, w2):
        return _ref_moe(x, dict(router=router, w1=w1, w3=w3, w2=w2), bias,
                        c)[0]

    args = (x, router, p["w1"], p["w3"], p["w2"])
    _check(got, want, args, tol=1e-4)
    if not want_rows:
        grads = jax.grad(lambda *a: jnp.sum(got(*a)), (0, 1, 2, 3, 4))(*args)
        assert not np.asarray(got(*args)).any()
        assert not any(np.asarray(g).any() for g in grads)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("share", [(8, 8, 64, 2, 0.0), (64, 8, 512, 8, 0.2)],
                         ids=["all_held_one_chunk", "an_eighth_three_chunks"])
def test_chunked_layer_is_the_whole_buffer_layer(share, dtype, monkeypatch):
    """A row's arithmetic is what it was before the chunks: operands in
    the data's type, products accumulated and handed back in float32 and
    rounded where they are next read, a token's k terms added in float32.
    Forward bit for bit against the whole-buffer evaluation; the gradients
    to one place of the type at the largest entry's size (XLA's CPU
    backend drops a rounding between two fused passes, so which sums are
    rounded twice differs with the program's structure, and terms
    cancel). The buffers start as NaN: nothing is taken from a row no
    pass wrote."""
    n, count, t, k, lift = share
    rs = np.random.RandomState(13)
    d, f = 16, 8
    x = _rand(rs, t, d).astype(dtype)
    router = _rand(rs, n, d, scale=0.5).astype(dtype)
    bias = jnp.zeros(n).at[:count].set(lift)
    p = {k_: v.astype(dtype) for k_, v in _moe_params(
        rs, dict(CONFIG, num_experts=n, experts_held=[0, count]), d, f,
        (0, count)).items()}
    sel, w = moe.route(x, router, bias, k, route_scale=2.826)
    counts = jnp.bincount(sel.reshape(-1), length=n).astype(jnp.int32)
    assert int(moe.chunk_load(counts[:count], t * k, n)[0]) \
        == (1 if count == n else 2 * 3)
    monkeypatch.setattr(jax.lax, "empty", lambda shape, dtype: jnp.full(
        shape, jnp.nan, dtype))
    moe.held_experts_ffn.clear_cache()

    def chunked(x, w1, w3, w2):
        return moe.held_experts_ffn(x, sel, w, counts, w1, w3, w2)

    def plain(x, w1, w3, w2):
        return _plain_whole_buffer(x, sel, w, counts, w1, w3, w2)

    def grads(fn):
        return jax.grad(lambda *a: jnp.sum(fn(*a).astype(jnp.float32) ** 2),
                        (0, 1, 2, 3))(*rest)

    rest = (x, p["w1"], p["w3"], p["w2"])
    try:
        np.testing.assert_array_equal(chunked(*rest), plain(*rest))
        tol = 2e-5 if dtype == "float32" else 2 ** -7
        for a, b in zip(grads(chunked), grads(plain)):
            assert np.isfinite(np.asarray(a, np.float32)).all()
            np.testing.assert_allclose(
                np.asarray(a, np.float32), np.asarray(b, np.float32),
                rtol=tol, atol=tol * float(jnp.max(jnp.abs(b))))
    finally:
        moe.held_experts_ffn.clear_cache()


@pytest.mark.parametrize("share", [(64, 8), (64, 32), (64, 64)],
                         ids=lambda s: "%d_of_%d" % s[::-1])
def test_chunked_layer_holds_one_body(share):
    """The program's size does not grow with the number of chunks: the
    layer's value and gradient, under ``jax.checkpoint`` as the step runs
    a layer, lower (for the TPU, where the grouped product is an op of
    its own) to 12 grouped products, as the whole-buffer layer did (3
    forward, 3 made again, 6 transposed), each over the whole sorted
    order, and to one loop for each pass over the sorted rows (forward
    and made again: two dispatches, gate, weights; pulled back: the
    combine's transpose, weights, gate, and the rounding of each of the
    two cotangents of the dispatched rows, the second added to the
    first), whatever ``T k / chunk_rows`` is; no branch holds a second
    copy."""
    n, count = share
    t, k, d, f = 512, 8, 16, 8
    assert t * k // moe.chunk_rows(t * k, count, n) == n // count

    def loss(x, router, w1, w3, w2):
        out = moe.moe_layer(x, router, jnp.zeros(n), w1, w3, w2, k,
                            (0, count))[0]
        return jnp.sum(out.astype(jnp.float32))

    args = (jnp.ones((t, d)), jnp.ones((n, d)), jnp.ones((count, d, f)),
            jnp.ones((count, d, f)), jnp.ones((count, f, d)))
    text = jax.jit(jax.value_and_grad(jax.checkpoint(loss), (0, 1, 2, 3, 4))
                   ).trace(*args).lower(lowering_platforms=("tpu",)
                                        ).as_text()
    assert text.count('"chlo.ragged_dot"') == 12
    assert text.count("stablehlo.while") == 4 + 4 + 5
    assert "stablehlo.case" not in text and "stablehlo.if" not in text


def test_layers_of_one_shape_share_one_body():
    """A model's expert layers are of one shape: they are calls of one
    lowered function, so the step program holds the layer's passes once
    however many layers it has (and is traced once for all of them)."""
    n, count, t, k, d, f = 64, 8, 512, 8, 16, 8

    def loss(x, router, w1, w3, w2):
        for _ in range(3):
            x = x + jax.checkpoint(lambda x: moe.moe_layer(
                x, router, jnp.zeros(n), w1, w3, w2, k, (0, count))[0])(x)
        return jnp.sum(x)

    args = (jnp.ones((t, d)), jnp.ones((n, d)), jnp.ones((count, d, f)),
            jnp.ones((count, d, f)), jnp.ones((count, f, d)))
    text = jax.jit(jax.value_and_grad(loss, (0, 1, 2, 3, 4))).trace(
        *args).lower(lowering_platforms=("tpu",)).as_text()
    assert text.count('"chlo.ragged_dot"') == 12
    assert text.count("stablehlo.while") == 4 + 4 + 5


# -- the whole model through Module.fit --------------------------------------

def _fit_three_steps():
    from mxnet_tpu.io import DataBatch, DataDesc, DataIter
    B, T = 2, 16
    sym = afmoe.get_symbol(dtype="float32", **CONFIG)
    shapes, _, aux_shapes = sym.infer_shape(data=(B, T), label=(B, T))
    rs = np.random.RandomState(9)
    params = {n: (np.ones(s) + 0.1 * rs.randn(*s) if n.endswith("gamma")
                  else rs.randn(*s) * 0.05).astype(np.float32)
              for n, s in zip(sym.list_arguments(), shapes)
              if n not in ("data", "label")}
    aux = {n: (rs.randn(*s) * 0.01 if n.endswith("bias")
               else np.zeros(s)).astype(np.float32)
           for n, s in zip(sym.list_auxiliary_states(), aux_shapes)}
    ids = rs.randint(0, 40, (3, B, T + 1))
    batches = [(b[:, :-1].astype(np.int32), b[:, 1:].astype(np.float32))
               for b in ids]

    class Feed(DataIter):
        def __init__(self):
            super().__init__(B)
            self.i = 0
        provide_data = property(
            lambda s: [DataDesc("data", (B, T), dtype=np.int32)])
        provide_label = property(lambda s: [DataDesc("label", (B, T))])

        def reset(self):
            self.i = 0

        def next(self):
            if self.i >= len(batches):
                raise StopIteration
            self.i += 1
            return DataBatch([batches[self.i - 1][0]],
                             [batches[self.i - 1][1]], pad=0)

    mod = mx.mod.Module(sym, data_names=("data",), label_names=("label",),
                        context=mx.cpu())
    losses, first = [], {}

    def cb(p):
        losses.append(float(np.mean(mod.get_outputs()[0].asnumpy())))
        if p.nbatch == 0:
            for i, n in enumerate(mod._param_names):
                st = mod._updater.states[i]
                mean = st[0][0] if isinstance(st[0], tuple) else st[0]
                first[n] = float(np.linalg.norm(mean.asnumpy())) / 0.1

    opt = dict(learning_rate=1e-3, beta1=0.9, beta2=0.95, epsilon=1e-8,
               wd=0.0, rescale_grad=1.0 / (B * T), multi_precision=True)
    mod.fit(Feed(), arg_params={k: mx.nd.array(v) for k, v in params.items()},
            aux_params={k: mx.nd.array(v) for k, v in aux.items()},
            initializer=None, eval_metric=mx.metric.create("loss"),
            num_epoch=1, kvstore="local", optimizer="adam",
            optimizer_params=opt, batch_end_callback=cb)
    return mod, params, aux, batches, losses, first


def test_module_fit_matches_reference():
    """Three steps of ``Module.fit`` (one fused dispatch a batch, Adam,
    the bias written inside the step) against the reference: loss, first
    gradient, parameter change, bias change."""
    from mxnet_tpu import telemetry
    before = telemetry.counters().get("dispatch.train_step", 0)
    mod, params, aux, batches, losses, first = _fit_three_steps()
    assert mod._fused_fallback_reason is None
    assert telemetry.counters()["dispatch.train_step"] - before == 3
    want = ref.run_steps(params, aux, batches, 1e-3, 0.0, 0.0, config=CONFIG,
                         beta1=0.9, beta2=0.95, epsilon=1e-8)
    np.testing.assert_allclose(losses, want["losses"], rtol=2e-5)
    arg, auxp = mod.get_params()
    for k in params:
        assert abs(first[k] - want["grad_norms"][k]) \
            <= 2e-3 * want["grad_norms"][k] + 1e-7, k
        change = float(np.linalg.norm(arg[k].asnumpy() - params[k]))
        assert abs(change - want["change_norms"][k]) \
            <= 2e-3 * want["change_norms"][k], k
    for k, v in want["aux_change_norms"].items():
        assert v > 0
        change = float(np.linalg.norm(auxp[k].asnumpy() - aux[k]))
        assert abs(change - v) <= 1e-4 * v, k
    # the load the layers summed on the device, published at the epoch's end
    load = auxp["l1_moe_load_running_sum"].asnumpy()
    assert load[0] == 3 and 0 < load[2] <= load[1] <= 3 * 2 * 16 * 2


def test_fit_publishes_moe_counters():
    from mxnet_tpu import telemetry
    before = dict(telemetry.counters())
    mod, *_ = _fit_three_steps()
    now = telemetry.counters()
    assert now["moe.steps"] - before.get("moe.steps", 0) == 3 * 3
    held = now["moe.rows_held"] - before.get("moe.rows_held", 0)
    fullest = now["moe.rows_max"] - before.get("moe.rows_max", 0)
    assert 0 < fullest <= held <= 9 * 2 * 16 * 2
    mod._publish_aux_counters()         # nothing new: nothing added
    assert telemetry.counters()["moe.steps"] == now["moe.steps"]


def test_fit_publishes_chunk_counters():
    """Beside ``moe.rows_held``: the chunks of the sorted order the layers
    ran and the held rows past a layer-step's first chunk. At this size a
    chunk is the whole order (2 x 16 x 2 selections are under one tile),
    so each of the 3 layers' 3 steps runs one chunk and nothing is past
    it; the sums' arithmetic at a load of several chunks is the op's."""
    from mxnet_tpu import telemetry
    before = dict(telemetry.counters())
    _fit_three_steps()
    now = telemetry.counters()
    assert now["moe.rows_held"] > before.get("moe.rows_held", 0)
    assert now["moe.chunks_run"] - before.get("moe.chunks_run", 0) == 3 * 3
    assert now.get("moe.rows_overflow", 0) \
        == before.get("moe.rows_overflow", 0)
    # 4,096 selections, 8 of 64 experts held: chunks of 512 rows; 1,301
    # rows and the tile after them reach into the second stair of three
    counts = jnp.zeros(64, jnp.int32).at[8:16].set(
        jnp.asarray([171, 162, 155, 163, 155, 168, 159, 168]))
    new = get_op("_contrib_MoE").stateful_update(
        [jnp.zeros((1, 512, 16)), None, None, None, None, jnp.zeros(64),
         jnp.asarray([4.0, 10.0, 5.0, 6.0, 7.0])], (None, counts),
        dict(num_experts=64, top_k=8, experts_held=(8, 8), _train=True,
             load_balance_coeff=0.001))
    _close(new[6], [5, 10 + 1301, 5 + 171, 6 + 2 * 3, 7 + 1301 - 512])


def test_mirror_stages_cut_one_segment_a_layer():
    """The symbol marks each layer's first node; the executor checkpoints
    one segment a layer whatever MXNET_BACKWARD_DO_MIRROR says, and the
    gradients are those of the unsegmented graph."""
    from mxnet_tpu.executor import _GraphProgram, MIRROR_STAGE
    sym = afmoe.get_symbol(dtype="float32", **CONFIG)
    prog = _GraphProgram(sym)
    assert prog.mirror_stages
    marked = [n.name for n in prog.nodes
              if n.op is not None and MIRROR_STAGE in n._extra_attrs]
    assert marked == ["l0_attn_norm", "l1_attn_norm", "l2_attn_norm",
                      "l3_attn_norm", "final_norm"]
    assert not _GraphProgram(mx.sym.FullyConnected(
        mx.sym.Variable("data"), num_hidden=4)).mirror_stages


def test_loss_metric_rides_in_the_fused_step():
    """``mx.metric.Loss`` has a device kernel: the fit loop makes no host
    sync a batch for it, and the mean it reports is ``update``'s."""
    mod, _, _, _, losses, _ = _fit_three_steps()
    assert mod._fused_plan["kernel"] is not None
    metric = mod._fused_plan["metric"]
    name, value = metric.get()
    assert name == "loss" and metric.num_inst == 3 * 2 * 16
    np.testing.assert_allclose(value, np.mean(losses), rtol=1e-5)
    host = mx.metric.Loss()
    host.update(None, [mx.nd.array(np.full((2, 16), 1.5, np.float32))])
    assert host.get() == ("loss", 1.5)
