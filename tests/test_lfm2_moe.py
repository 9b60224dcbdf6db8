"""LFM2's gated short convolution (``_contrib_GatedShortConv``), grouped-query
attention at head width 64, the tied head and the ``lfm2_moe`` symbol, each
against a plain form or the plain reference
``benchmarks/reference/lfm2_moe.py`` (float32, small sizes, seeded random
weights, the kernels interpreted)."""
import importlib.util
import json
import math
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu.ops import lm
from mxnet_tpu.parallel import moe

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(path, name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(ROOT, path))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load("benchmarks/reference/lfm2_moe.py", "lfm2_moe_reference")
lfm2 = _load("examples/language-model/symbols/lfm2_moe.py", "lfm2_moe_symbol")
correct = _load("benchmarks/harness/correct.py", "bench_correct")

CONFIG = dict(
    hidden_size=32, layer_types=["conv", "conv", "full_attention", "conv"],
    num_hidden_layers=4, num_dense_layers=1, intermediate_size=48,
    conv_L_cache=3, num_attention_heads=4, num_key_value_heads=2, head_dim=8,
    rope_theta=1e6, num_experts=8, num_experts_per_tok=2,
    moe_intermediate_size=16, score_func="sigmoid", route_norm=True,
    route_scale=1.0, load_balance_coeff=0.001, rms_norm_eps=1e-5,
    vocab_size=40, experts_held=[2, 4])


def _rand(rs, *shape, scale=1.0):
    return jnp.asarray(rs.randn(*shape).astype(np.float32) * scale)


def _close(a, b, tol=2e-5):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=tol,
                               atol=tol)


def _check(op_fn, ref_fn, args, tol=2e-5):
    """Forward and the gradient of a fixed projection of the output."""
    out, want = op_fn(*args), ref_fn(*args)
    assert bool(jnp.all(jnp.isfinite(out)))
    _close(out, want, tol)
    w = jnp.cos(jnp.arange(want.size, dtype=jnp.float32)).reshape(want.shape)
    nums = tuple(range(len(args)))
    got = jax.grad(lambda *a: jnp.sum(op_fn(*a) * w), nums)(*args)
    exp = jax.grad(lambda *a: jnp.sum(ref_fn(*a) * w), nums)(*args)
    for g, e in zip(got, exp):
        assert bool(jnp.all(jnp.isfinite(g)))
        _close(g, e, tol * max(1.0, float(jnp.max(jnp.abs(e)))))


# -- the gated short convolution ------------------------------------------------

def _loop(data, weight):
    """``C * conv(B * x)`` a position and a tap at a time."""
    d, k = weight.shape
    b, c, x = data[..., :d], data[..., d:2 * d], data[..., 2 * d:]
    z = b * x
    out = []
    for t in range(data.shape[1]):
        s = jnp.zeros(data.shape[:1] + (d,))
        for j in range(k):
            if t - (k - 1) + j >= 0:
                s = s + weight[:, j] * z[:, t - (k - 1) + j]
        out.append(c[:, t] * s)
    return jnp.stack(out, axis=1)


@pytest.mark.parametrize("t,d,k", [(9, 40, 3), (5, 136, 3), (7, 8, 4)],
                         ids=lambda v: str(v))
def test_gated_short_conv_matches_a_plain_loop(t, d, k):
    """Forward and both gradients, at the published width of 3 and another,
    channels a multiple of 128 or not, T shorter than a few taps or not."""
    rs = np.random.RandomState(t + d)
    args = (_rand(rs, 2, t, 3 * d), _rand(rs, d, k))

    def op(data, w):
        return lm.gated_short_conv(data, w, kernel=k)

    _check(op, _loop, args)


def test_gated_short_conv_in_a_symbol():
    """The taps' shape is inferred from the input's third and the width;
    the op takes the taps in float32 beside bfloat16 data and hands back
    the data's type."""
    sym = mx.sym._contrib_GatedShortConv(mx.sym.Variable("x"), name="s",
                                         kernel=3)
    assert sym.list_arguments() == ["x", "s_weight"]
    shapes, outs, _ = sym.infer_shape(x=(2, 5, 24))
    assert shapes[1] == (8, 3) and outs[0] == (2, 5, 8)
    out = lm.gated_short_conv(jnp.ones((1, 4, 6), jnp.bfloat16),
                              jnp.ones((2, 3), jnp.float32))
    assert out.dtype == jnp.bfloat16


def test_causal_conv1d_without_an_activation_through_a_symbol():
    """``act_type=None`` passes through a symbol's attributes and means no
    activation there too (the short convolution's taps have none)."""
    rs = np.random.RandomState(3)
    x, w = rs.randn(2, 7, 6).astype(np.float32), \
        rs.randn(6, 3).astype(np.float32)
    sym = mx.sym._contrib_CausalConv1D(mx.sym.Variable("x"), name="c",
                                       kernel=3, no_bias=True, act_type=None)
    ex = sym.bind(mx.cpu(), {"x": mx.nd.array(x), "c_weight": mx.nd.array(w)})
    got = ex.forward()[0].asnumpy()
    padded = np.pad(x, ((0, 0), (2, 0), (0, 0)))
    want = sum(padded[:, i:i + 7] * w[:, i] for i in range(3))
    _close(got, want, 1e-5)


def test_the_convolutions_share_one_body():
    """The short convolution and ``_contrib_CausalConv1D`` run the same
    shifted products: the mixer with B = C = 1 is the plain convolution."""
    rs = np.random.RandomState(4)
    x, w = _rand(rs, 2, 9, 16), _rand(rs, 16, 3)
    ones = jnp.ones_like(x)
    _close(lm.gated_short_conv(jnp.concatenate([ones, ones, x], -1), w),
           lm.causal_conv1d(x, w, kernel=3, act_type=None, no_bias=True),
           1e-6)


def test_shortconv_counts_its_runs():
    from mxnet_tpu import telemetry
    before = dict(telemetry.counters())
    lm._shortconv_count_runs((2, 75, 32), {"kernel": 3}, 3)
    lm._shortconv_count_steps(3)
    now = telemetry.counters()
    assert now["shortconv.runs"] - before.get("shortconv.runs", 0) == 3
    assert now["shortconv.steps"] - before.get("shortconv.steps", 0) == 3
    assert {"shortconv.runs", "shortconv.steps"} <= set(telemetry.COUNTERS)


# -- attention at head width 64 --------------------------------------------------

def _plain_gqa(q, k, v, heads, kv_heads):
    b, t, _ = q.shape
    qh = q.reshape(b, t, heads, -1)
    kh, vh = (jnp.repeat(x.reshape(b, t, kv_heads, -1), heads // kv_heads,
                         axis=2) for x in (k, v))
    # the reference's masked softmax, a block of queries at a time
    return jnp.stack([ref.attention(*a) for a in zip(qh, kh, vh)])


def check_gqa_at_64(t, dtype, tol, heads=32, kv_heads=8, seed=0):
    """``_contrib_CausalAttention`` (the flash kernels) with ``heads`` query
    heads of 64 on ``kv_heads`` K/V heads against plain softmax attention
    in float32: the output and the three gradients, each to ``tol`` of its
    largest magnitude. What the chip's lane runs (``tests/tpu``) at the
    cell's shapes; returns the worst gaps."""
    rs = np.random.RandomState(seed)
    args = tuple(_rand(rs, 1, t, h * 64).astype(dtype)
                 for h in (heads, kv_heads, kv_heads))
    w = jnp.cos(jnp.arange(t * heads * 64, dtype=jnp.float32)
                ).reshape(1, t, -1)

    def op(q, k, v):
        return lm.causal_attention(q, k, v, num_heads=heads,
                                   num_kv_heads=kv_heads)

    def want(q, k, v):
        return _plain_gqa(*(x.astype(jnp.float32) for x in (q, k, v)),
                          heads, kv_heads)

    got, pull = jax.vjp(jax.jit(op), *args)
    with jax.default_matmul_precision("highest"):
        exp, pull_exp = jax.vjp(jax.jit(want), *args)
        grads = pull_exp(w)
    assert got.dtype == dtype

    def worst(a, e):
        a, e = (np.asarray(x, np.float32) for x in (a, e))
        return float(np.max(np.abs(a - e)) / np.max(np.abs(e)))

    gaps = [worst(got, exp)] + [worst(a, e) for a, e in
                                zip(pull(w.astype(dtype)), grads)]
    assert max(gaps) < tol, gaps
    return gaps


def test_flash_kernels_at_head_width_64_on_grouped_heads():
    """32 query heads of 64 on 8 K/V heads (the cell's attention), the
    kernels interpreted, float32 at a T that is no multiple of the tile:
    the kernels' bodies take a half-lane head as they are."""
    check_gqa_at_64(40, jnp.float32, 2e-5)


# -- the share ties to the model ------------------------------------------------

def test_four_shares_add_up_to_the_whole_layer():
    """32 experts in 4 shares of 8, top-4: the four shares' routed parts
    give the uncut reference's feed-forward (no shared expert)."""
    rs = np.random.RandomState(7)
    d, f, n = 32, 16, 32
    c = dict(CONFIG, num_experts=n, num_experts_per_tok=4, experts_held=None)
    p = {"l1_moe_router_weight": _rand(rs, n, d, scale=0.3),
         "l1_moe_expert_w1_weight": _rand(rs, n, d, f, scale=0.2),
         "l1_moe_expert_w3_weight": _rand(rs, n, d, f, scale=0.2),
         "l1_moe_expert_w2_weight": _rand(rs, n, f, d, scale=0.2)}
    x, bias = _rand(rs, 30, d), _rand(rs, n, scale=0.05)
    with jax.default_matmul_precision("highest"):
        whole, _ = ref.routed(x, p, bias, "l1_moe", c, None)
    total = jnp.zeros_like(whole)
    for first in range(0, n, 8):
        held = slice(first, first + 8)
        part, counts = moe.moe_layer(
            x, p["l1_moe_router_weight"], bias,
            p["l1_moe_expert_w1_weight"][held],
            p["l1_moe_expert_w3_weight"][held],
            p["l1_moe_expert_w2_weight"][held], top_k=4,
            experts_held=(first, 8))
        assert int(counts.sum()) == 30 * 4      # every share routes alike
        total = total + part
    _close(total, whole, tol=1e-4)


# -- the symbol ------------------------------------------------------------------

def test_leaf_types_follow_the_compute_type():
    """Without shapes, as the benchmark's window asks, every leaf's type is
    the one the Module binds: matrices and the taps' offsets in the compute
    type, the norms' scales float32, whatever float32 start enters a mixer
    before them."""
    sym = lfm2.get_symbol(dtype="bfloat16", **CONFIG)
    types, _, aux = sym.infer_type(data=np.int32, label=np.float32)
    asked = dict(zip(sym.list_arguments(), types))
    for name, t in asked.items():
        if name in ("data", "label"):
            continue
        f32 = name.endswith("_gamma")
        assert str(np.dtype(t)) == ("float32" if f32 else "bfloat16"), name
    assert all(np.dtype(t) == np.float32 for t in aux)
    mod = mx.mod.Module(sym, data_names=("data",), label_names=("label",),
                        context=mx.cpu())
    mod.bind(data_shapes=[("data", (2, 20), np.int32)],
             label_shapes=[("label", (2, 20))])
    for name, arr in mod._exec.arg_dict.items():
        if name not in ("data", "label"):
            assert str(arr.dtype) == str(np.dtype(asked[name])), name


def test_the_symbol_names_its_parts():
    from mxnet_tpu.executor import _GraphProgram, MIRROR_STAGE
    prog = _GraphProgram(lfm2.get_symbol(dtype="float32", **CONFIG))
    marked = [n.name for n in prog.nodes
              if n.op is not None and MIRROR_STAGE in n._extra_attrs]
    assert marked == [p + s for p in ("l0_", "l1_", "l2_", "l3_")
                      for s in ("attn_norm", "ffn_norm")] + ["final_norm"]
    names = {n.name for n in prog.nodes if n.op is not None}
    assert {"l0_conv_in", "l0_shortconv", "l0_conv_out", "l0_ffn_w1",
            "l1_moe", "l2_attn_core", "l2_attn_qnorm", "l2_attn_knorm",
            "l2_attn_qrope", "l3_shortconv"} <= names
    assert not {"l2_shortconv", "l0_attn_core", "l0_moe"} & names
    sym = lfm2.get_symbol(dtype="float32", **CONFIG)
    shapes = dict(zip(sym.list_arguments(),
                      sym.infer_shape(data=(1, 16), label=(1, 16))[0]))
    # the head is the embedding: no matrix of its own
    assert [k for k in shapes if k.endswith("_weight") and
            shapes[k] == (40, 32)] == ["embed_weight"]
    assert shapes["l0_conv_in_weight"] == (3 * 32, 32)
    assert shapes["l0_shortconv_weight_offset"] == (32, 3)
    assert shapes["l2_attn_wk_weight"] == (2 * 8, 32)
    assert shapes["l2_attn_qnorm_gamma"] == (8,)
    assert shapes["l1_moe_expert_w1_weight"] == (4, 32, 16)
    assert shapes["l1_moe_router_weight"] == (8, 32)


def test_taps_start_by_index():
    """The taps start over +-1/sqrt(3), symbol and reference alike."""
    taps = np.asarray(ref.conv_start(2048, 3))
    bound = 1 / math.sqrt(3)
    assert -bound < taps.min() < -0.99 * bound
    assert 0.99 * bound < taps.max() < bound
    sym = lfm2._taps("s", 8, dict(conv_L_cache=3, dtype="float32"))
    ex = sym.bind(mx.cpu(), {"s_weight_offset": mx.nd.zeros((8, 3))})
    _close(ex.forward()[0].asnumpy(), ref.conv_start(8, 3), 1e-7)


def test_the_convolution_carries_the_mixer():
    """At the start the convolution's window is a large part of a mixer's
    output: the mixer with the taps drawn about nought (the offsets alone)
    reads far from the mixer at its start, so a comparison sees the
    window."""
    rs = np.random.RandomState(11)
    d = 32
    u = _rand(rs, 24, d)
    p = {"l0_conv_in_weight": _rand(rs, 3 * d, d, scale=0.2),
         "l0_conv_out_weight": _rand(rs, d, d, scale=0.2),
         "l0_shortconv_weight_offset": _rand(rs, d, 3, scale=0.02)}
    c = dict(CONFIG, hidden_size=d)
    with jax.default_matmul_precision("highest"):
        at_start = ref.short_conv(u, p, "l0_", c, None, None)
        offsets = p["l0_shortconv_weight_offset"] - ref.conv_start(d, 3)
        alone = ref.short_conv(u, dict(p, l0_shortconv_weight_offset=offsets),
                               "l0_", c, None, None)
    assert float(jnp.linalg.norm(alone)) < 0.1 * float(
        jnp.linalg.norm(at_start))


# -- the tied head -------------------------------------------------------------

def test_the_tied_weight_takes_the_sum_of_both_uses(monkeypatch):
    """The program's gradient of ``embed_weight`` (one leaf, read by the
    lookup and by the head) is the reference's gradient of the lookup's
    use plus that of the head's, the two told apart as two matrices."""
    rs = np.random.RandomState(12)
    B, T = 2, 12
    # the executor's backward runs after the forward pass has written the
    # selection bias: with a rate of nought it routes as the forward did
    c = dict(CONFIG, load_balance_coeff=0.0)
    sym = lfm2.get_symbol(dtype="float32", **c)
    shapes, _, aux_shapes = sym.infer_shape(data=(B, T), label=(B, T))
    params = {n: (np.ones(s) if n.endswith("gamma")
                  else rs.randn(*s) * 0.1).astype(np.float32)
              for n, s in zip(sym.list_arguments(), shapes)
              if n not in ("data", "label")}
    aux = {n: np.zeros(s, np.float32)
           for n, s in zip(sym.list_auxiliary_states(), aux_shapes)}
    ids = rs.randint(0, 40, (B, T + 1))
    x, y = ids[:, :-1].astype(np.int32), ids[:, 1:].astype(np.float32)
    ex = sym.simple_bind(mx.cpu(), data=(B, T), label=(B, T),
                         type_dict={"data": np.int32},
                         grad_req={k: "null" if k in ("data", "label")
                                   else "write"
                                   for k in sym.list_arguments()})
    for k, v in params.items():
        ex.arg_dict[k][:] = v
    for k, v in aux.items():
        ex.aux_dict[k][:] = v
    ex.arg_dict["data"][:] = x
    ex.arg_dict["label"][:] = y
    ex.forward(is_train=True)
    ex.backward()
    got = ex.grad_dict["embed_weight"].asnumpy()

    monkeypatch.setattr(ref, "head_matrix", lambda p, fault=None: p["head"])

    def loss(lookup, head):
        p = dict({k: jnp.asarray(v) for k, v in params.items()},
                 embed_weight=lookup, head=head)
        # the op's gradient is that of the sum over positions
        return ref.loss_fn(p, aux, x, y.astype(np.int32), c)[0] * B * T

    e = jnp.asarray(params["embed_weight"])
    with jax.default_matmul_precision("highest"):
        g_lookup, g_head = jax.grad(loss, (0, 1))(e, e)
    _close(got, g_lookup + g_head, 1e-4)
    for part in (g_lookup, g_head):
        assert float(jnp.max(jnp.abs(got - part))) > 1e-2


# -- the whole model through Module.fit ---------------------------------------

def _fit_three_steps():
    from mxnet_tpu.io import DataBatch, DataDesc, DataIter
    B, T = 2, 20
    sym = lfm2.get_symbol(dtype="float32", **CONFIG)
    shapes, _, aux_shapes = sym.infer_shape(data=(B, T), label=(B, T))
    rs = np.random.RandomState(9)
    params = {n: (np.ones(s) + 0.1 * rs.randn(*s) if n.endswith("gamma")
                  else rs.randn(*s) * 0.05).astype(np.float32)
              for n, s in zip(sym.list_arguments(), shapes)
              if n not in ("data", "label")}
    # a selection bias of a few hundredths: the biased scores differ from
    # the unbiased from the first step
    aux = {n: (rs.randn(*s) * 0.05 if n.endswith("bias")
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


@pytest.fixture(scope="module")
def fitted():
    from mxnet_tpu import telemetry
    before = dict(telemetry.counters())
    return _fit_three_steps() + (before, dict(telemetry.counters()))


def test_module_fit_matches_reference(fitted):
    """Three steps of ``Module.fit`` (one fused dispatch a batch, Adam,
    the bias written inside the step) against the reference: loss, every
    leaf's first gradient, every leaf's change, every bias's change. Both
    sides compute in float32; the tolerances hold what the two orders of
    summation leave (losses to 2e-5, gradients and changes to 2e-3 of the
    leaf or of a thousandth of the median leaf), where bfloat16 operands
    would read 1e-3 on the losses and several hundredths on the
    gradients."""
    mod, params, aux, batches, losses, first, before, now = fitted
    assert mod._fused_fallback_reason is None
    assert now["dispatch.train_step"] \
        - before.get("dispatch.train_step", 0) == 3
    want = ref.run_steps(params, aux, batches, 1e-3, 0.0, 0.0, config=CONFIG,
                         beta1=0.9, beta2=0.95, epsilon=1e-8)
    np.testing.assert_allclose(losses, want["losses"], rtol=2e-5)
    arg, auxp = mod.get_params()
    floor = 1e-3 * float(np.median(list(want["grad_norms"].values())))
    assert sorted(first) == sorted(want["grad_norms"]) == sorted(params)
    for k in params:
        assert abs(first[k] - want["grad_norms"][k]) \
            <= 2e-3 * max(want["grad_norms"][k], floor) + 1e-7, k
        change = float(np.linalg.norm(arg[k].asnumpy() - params[k]))
        assert abs(change - want["change_norms"][k]) \
            <= 2e-3 * want["change_norms"][k], k
    assert sorted(want["aux_change_norms"]) == ["l1_moe_bias", "l2_moe_bias",
                                                "l3_moe_bias"]
    for k, v in want["aux_change_norms"].items():
        assert v > 0
        change = float(np.linalg.norm(auxp[k].asnumpy() - aux[k]))
        assert abs(change - v) <= 1e-4 * v, k


def test_bfloat16_operands_fail_the_tolerances(fitted):
    """The tolerances above tell the precisions apart: the reference with
    every product's operands rounded to bfloat16 reads outside them."""
    _, params, aux, batches, *_ = fitted
    want = ref.run_steps(params, aux, batches, 1e-3, 0.0, 0.0, config=CONFIG,
                         beta1=0.9, beta2=0.95, epsilon=1e-8)

    def bf16(x):
        return x.astype(jnp.bfloat16).astype(jnp.float32)

    low = ref.run_steps(params, aux, batches, 1e-3, 0.0, 0.0, config=CONFIG,
                        beta1=0.9, beta2=0.95, epsilon=1e-8,
                        operand_round=bf16)
    loss_gap = max(abs(a - b) / abs(b)
                   for a, b in zip(low["losses"], want["losses"]))
    grad_gap = max(abs(low["grad_norms"][k] - v) / v
                   for k, v in want["grad_norms"].items())
    assert loss_gap > 2e-5 or grad_gap > 2e-3, (loss_gap, grad_gap)


def test_fit_publishes_shortconv_and_moe_counters(fitted):
    """Three conv layers and three expert layers, three steps:
    ``shortconv.runs`` 9 and ``shortconv.steps`` 3 (counted once a fused
    step, for all mixers), beside the routed layers' ``moe.*``; the op
    holds no auxiliary state."""
    mod, *_, before, now = fitted

    def grew(k):
        return now.get(k, 0) - before.get(k, 0)

    assert grew("shortconv.runs") == 3 * 3
    assert grew("shortconv.steps") == 3
    assert grew("moe.steps") == 3 * 3
    _, auxp = mod.get_params()
    assert not [k for k in auxp if "conv" in k]


# -- the reference's planted faults -------------------------------------------

@pytest.fixture(scope="module")
def sound(fitted):
    _, params, aux, batches, *_ = fitted
    kw = dict(config=CONFIG, beta1=0.9, beta2=0.95, epsilon=1e-8)
    return (params, aux, batches, kw,
            ref.run_steps(params, aux, batches, 1e-3, 0.0, 0.0, **kw))


def _caught(got, want):
    with open(os.path.join(ROOT, "benchmarks", "limits",
                           "lfm2_moe.fit.json")) as f:
        limits = json.load(f)["rehearse"]
    ok, table = correct.judge(correct.compare(got, want), limits)
    return not ok, table


@pytest.mark.parametrize("fault", ref.FAULTS + ("control",))
def test_every_planted_fault_is_caught(sound, fault):
    """Each wrong mechanism, and the control one step of precision below,
    reads over at least one of the rehearsal's limits against the sound
    reference; the sound reference against itself reads nought."""
    params, aux, batches, kw, want = sound
    variant = ref.CONTROL if fault == "control" else dict(fault=fault)
    got = ref.run_steps(params, aux, batches, 1e-3, 0.0, 0.0, **kw,
                        **variant)
    caught, table = _caught(got, want)
    assert caught, (fault, table)
    assert not _caught(want, want)[0]
