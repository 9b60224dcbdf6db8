"""Kimi Delta Attention's recurrence (``_contrib_KDA``), latent attention
with keys wider than values (``_contrib_CausalAttention``), the gated
norm's sigmoid form, the convolution without a bias and the
``kimi_linear`` symbol, each against the plain reference
``benchmarks/reference/kimi_linear.py`` (float32, small sizes, seeded
random weights, the kernels interpreted)."""
import importlib.util
import json
import math
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu.ops import get_op, lm
from mxnet_tpu.parallel import moe

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(path, name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(ROOT, path))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load("benchmarks/reference/kimi_linear.py", "kimi_linear_reference")
kimi_linear = _load("examples/language-model/symbols/kimi_linear.py",
                    "kimi_linear_symbol")
correct = _load("benchmarks/harness/correct.py", "bench_correct")

CONFIG = dict(
    hidden_size=32, layer_types=["kda", "kda", "mla", "kda"],
    num_hidden_layers=4, num_dense_layers=1, intermediate_size=48,
    kda_num_heads=2, kda_head_dim=16, short_conv_kernel_size=4, kda_chunk=16,
    kda_sub=8, num_attention_heads=2, kv_lora_rank=12, qk_nope_head_dim=8,
    qk_rope_head_dim=4, v_head_dim=8, num_experts=8, num_experts_per_tok=2,
    moe_intermediate_size=16, num_shared_experts=1, score_func="sigmoid",
    route_norm=True, route_scale=2.446, load_balance_coeff=0.001,
    rms_norm_eps=1e-5, vocab_size=40, experts_held=[2, 4])


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


# -- the recurrence -----------------------------------------------------------

H, DK, DV = 3, 8, 6


def _token_scan(q, k, v, gate, beta, a_log, dt_bias, per_head=False,
                dims=(H, DK, DV)):
    """The recurrence a token at a time, a sequence and a head at a time:
    the docstring of ``_contrib_KDA`` written out."""
    b, t, _ = q.shape
    H, DK, DV = dims

    def l2(x):
        return x / jnp.sqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)

    qn = l2(q.reshape(b, t, H, DK)) / math.sqrt(DK)
    kn = l2(k.reshape(b, t, H, DK))
    vv = v.reshape(b, t, H, DV)
    g = -jnp.exp(a_log)[:, None] * jax.nn.softplus(
        (gate + dt_bias).reshape(b, t, H, DK))
    if per_head:
        g = jnp.broadcast_to(jnp.mean(g, -1, keepdims=True), g.shape)
    bt = jax.nn.sigmoid(beta)

    def token(s, x):
        qt, kt, vt, gt, b_ = x
        s = jnp.exp(gt)[:, None] * s
        s = s + jnp.outer(b_ * kt, vt - kt @ s)
        return s, qt @ s

    def head(*x):
        return jax.lax.scan(token, jnp.zeros((DK, DV)), x)[1]

    per_sequence = jax.vmap(head, in_axes=1, out_axes=1)
    return jax.vmap(per_sequence)(qn, kn, vv, g, bt).reshape(b, t, H * DV)


def _kda_args(rs, batch, t, fast=2.0, dims=(H, DK, DV)):
    """Head 0 slow (A 1), head 1 fast: A 16 and a softplus argument about
    ``fast``, so a chunk's decays reach e^-2000 and ``exp(-G)`` alone would
    overflow after three tokens (more heads: A 4 and on round the three)."""
    h, dk, dv = dims
    return (_rand(rs, batch, t, h * dk), _rand(rs, batch, t, h * dk),
            _rand(rs, batch, t, h * dv), _rand(rs, batch, t, h * dk),
            _rand(rs, batch, t, h),
            jnp.log(jnp.asarray([1.0, 16.0, 4.0] * h)[:h]),
            _rand(rs, h * dk) + fast)


@pytest.mark.parametrize("t,chunk,sub", [(75, 64, 16), (75, 32, 16),
                                         (64, 16, 8), (40, 16, 16),
                                         (5, 64, 16), (33, 32, 4)],
                         ids=lambda v: str(v))
def test_kda_matches_the_token_scan(t, chunk, sub):
    """Forward and all seven gradients, T a multiple of the chunk or not
    (padded with positions that change no state), one sub-block a chunk or
    several, a fast head beside a slow one; nothing infinite anywhere."""
    args = _kda_args(np.random.RandomState(t + chunk), 2, t)

    def op(*a):
        return lm.kda(*a, heads=H, chunk=chunk, sub=sub)

    _check(op, _token_scan, args, tol=5e-5)


def test_kda_survives_decays_no_float_holds():
    """A softplus of 30 under A = 16: a token's decay is e^-480, nought in
    float32. The chunked form stays finite and agrees (every exponent it
    takes is a difference <= 0)."""
    args = _kda_args(np.random.RandomState(1), 1, 48, fast=30.0)
    out = lm.kda(*args, heads=H, chunk=16, sub=8)
    assert bool(jnp.all(jnp.isfinite(out)))
    _close(out, _token_scan(*args), 5e-5)
    grads = jax.grad(lambda *a: jnp.sum(lm.kda(*a, heads=H, chunk=16, sub=8)),
                     range(7))(*args)
    assert all(bool(jnp.all(jnp.isfinite(g))) for g in grads)


def test_a_decay_a_head_is_told_apart():
    """KDA read as a gated delta net (a head's mean decay for each of its
    channels) gives another output: the op follows the channels."""
    args = _kda_args(np.random.RandomState(2), 1, 64, fast=-1.0)
    out = lm.kda(*args, heads=H, chunk=16, sub=8)
    wrong = _token_scan(*args, per_head=True)
    assert float(jnp.max(jnp.abs(out - wrong))) > 100 * 5e-5
    _close(out, _token_scan(*args), 5e-5)


def test_kda_refuses_a_chunk_its_sub_blocks_do_not_divide():
    args = _kda_args(np.random.RandomState(3), 1, 8)
    with pytest.raises(ValueError, match="multiple of sub"):
        lm.kda(*args, heads=H, chunk=24, sub=16)
    with pytest.raises(ValueError, match="heads"):
        lm.kda(*args, heads=5, chunk=16, sub=8)


@pytest.mark.parametrize("t,sub,fast", [
    (32, 16, 2.0), (27, 8, 2.0), (27, 8, 30.0)], ids=lambda v: str(v))
def test_kda_intra_kernels_match_the_token_scan(t, sub, fast):
    """The kernel pair (``pallas/kda.py``, interpreted) inside the op, at
    chunks of 16: T whole chunks or not, one sub-block a chunk or two,
    decays a float holds or not (a softplus of 30 under A = 16: e^-480 a
    token). Forward and every input's gradient against the token scan;
    the op's ``kda/intra`` is the two kernels and nothing else."""
    args = _kda_args(np.random.RandomState(t + sub), 1, t, fast=fast)

    def op(*a):
        return lm.kda(*a, heads=H, chunk=16, sub=sub)

    text = str(jax.make_jaxpr(jax.grad(lambda *a: jnp.sum(op(*a)),
                                       tuple(range(7))))(*args))
    assert text.count("name=kda_intra_fwd") == 1
    assert text.count("name=kda_intra_bwd") == 1
    _check(op, _token_scan, args, tol=5e-5)


def test_the_kernels_inverse():
    """The unit lower block's inverse as the kernels make it (doubling at
    full precision) and its pull-back ``-inv^T g inv^T``, on the matrices
    the deleted ``jax.numpy`` inverse was tested on."""
    from mxnet_tpu.pallas import kda as kernels
    rs = np.random.RandomState(4)
    a = jnp.tril(_rand(rs, 3, 16, 16, scale=0.3), -1)
    w = _rand(rs, 3, 16, 16)
    for x, g, inv in zip(a, w, kernels._inverses(list(a))):
        _close(inv @ (jnp.eye(16) + x), jnp.eye(16), 1e-5)
        want = jax.grad(lambda y: jnp.sum(jnp.linalg.inv(jnp.eye(16) + y)
                                          * g))(x)
        _close(kernels._inverse_pullback(inv, g), want, 1e-4)


def check_kda_at_the_tile(t, dtype, dims):
    """``lm.kda`` at the kernels' tile (chunks of 64 in sub-blocks of 16)
    against the float32 token scan, forward and every input's gradient:
    to bfloat16's places (the products' operands rounded once each) in
    bfloat16. What the chip's lane runs (``tests/tpu``)."""
    h, dk, dv = dims
    args = _kda_args(np.random.RandomState(7), 1, t, dims=dims)
    args = tuple(x.astype(dtype) for x in args[:5]) + args[5:]
    w = jnp.cos(jnp.arange(t * h * dv, dtype=jnp.float32)).reshape(1, t, -1)

    def op(*a):
        return lm.kda(*a, heads=h, chunk=64, sub=16)

    def want(*a):
        return _token_scan(*(x.astype(jnp.float32) for x in a), dims=dims)

    out, pull = jax.vjp(op, *args)
    with jax.default_matmul_precision("highest"):
        exp, pull_exp = jax.vjp(want, *args)
        grads = pull_exp(w)
    assert out.dtype == dtype

    def worst(got, e):
        got, e = (np.asarray(v, np.float32) for v in (got, e))
        return float(np.max(np.abs(got - e)) / np.max(np.abs(e)))

    assert worst(out, exp) < 2e-2, worst(out, exp)
    for got, e in zip(pull(w.astype(dtype)), grads):
        assert bool(jnp.all(jnp.isfinite(got)))
        assert worst(got, e) < 3e-2, (got.shape, worst(got, e))


def test_a_mirrored_segment_runs_the_intra_kernel_where_needed():
    """Under the step's checkpoint policy the gradient of a node holds two
    ``kda_intra_fwd`` calls (the pull-back of ``kda/state`` and ``kda/out``
    needs ``Aqk``, ``W`` and ``U``, so the recomputed segment makes them
    again) and one ``kda_intra_bwd``; the only loops are the chunk loop of
    ``kda/state`` and its pull-back, over every chunk, and the kernels'
    over their heads: no slab of chunks is mapped and nothing inside the
    node is checkpointed again."""
    import re
    from mxnet_tpu import executor
    chunks = 32
    args = _kda_args(np.random.RandomState(6), 1, chunks * 8)

    def op(*a):
        return lm.kda(*a, heads=H, chunk=8, sub=8)

    def loss(*a):
        return jnp.sum(jax.checkpoint(op, policy=executor._MIRROR_POLICY)(*a))

    text = str(jax.make_jaxpr(jax.grad(loss, tuple(range(7))))(*args))
    assert text.count("name=kda_intra_fwd") == 2
    assert text.count("name=kda_intra_bwd") == 1
    # the loops: over the chunks, and inside the kernels over their heads
    lengths = re.findall(r"length=(\d+)", text)
    assert set(lengths) == {str(chunks), str(H)}, lengths
    assert text.count("prevent_cse") == 1


def test_no_other_cell_reaches_the_kda_kernels():
    """``_contrib_KDA`` (and so ``pallas/kda.py``) is emitted by the
    ``kimi_linear`` symbol alone: the three other cells' symbols hold no
    such node, so their lowered steps are the parent's (checked against
    the parent's text offline, PERF.md section 6, PR 37)."""
    afmoe = _load("examples/language-model/symbols/afmoe.py", "afmoe_sym")
    nemotron = _load("examples/language-model/symbols/nemotron_h.py",
                     "nemotron_sym")
    resnet = _load("examples/image-classification/symbols/resnet.py",
                   "resnet_sym")
    import test_lm_ops as lm_ops
    import test_nemotron_h as nem

    def ops(sym):
        return {n["op"] for n in json.loads(sym.tojson())["nodes"]}

    for sym in (afmoe.get_symbol(dtype="float32", **lm_ops.CONFIG),
                nemotron.get_symbol(dtype="float32", **nem.CONFIG),
                resnet.get_symbol(num_classes=10, num_layers=50,
                                  image_shape="3,32,32")):
        assert "_contrib_KDA" not in ops(sym)
    assert "_contrib_KDA" in ops(kimi_linear.get_symbol(dtype="float32",
                                                         **CONFIG))


def test_kda_counts_its_chunks():
    from mxnet_tpu import telemetry
    before = dict(telemetry.counters())
    lm._kda_count_steps((2, 75, H * DV), {"chunk": 32}, 3)
    now = telemetry.counters()
    assert now["kda.steps"] - before.get("kda.steps", 0) == 3
    assert now["kda.chunks_run"] - before.get("kda.chunks_run", 0) \
        == 3 * 2 * 3
    assert {"kda.steps", "kda.chunks_run"} <= set(telemetry.COUNTERS)


# -- the mixer's other ops ----------------------------------------------------

def test_causal_conv1d_without_a_bias():
    rs = np.random.RandomState(5)
    x, w = _rand(rs, 2, 9, 6), _rand(rs, 6, 4)

    def want(x, w):
        padded = jnp.pad(x, ((0, 0), (3, 0), (0, 0)))
        return jax.nn.silu(sum(padded[:, i:i + 9] * w[:, i]
                               for i in range(4)))

    def op(x, w):
        return lm.causal_conv1d(x, w, kernel=4, no_bias=True)

    _check(op, want, (x, w))
    sym = mx.sym._contrib_CausalConv1D(mx.sym.Variable("x"), name="c",
                                       kernel=4, no_bias=True)
    assert sym.list_arguments() == ["x", "c_weight"]


@pytest.mark.parametrize("act", ["sigmoid", "silu"])
def test_gated_rms_norm_with_the_norm_first(act):
    """``RMS_head(x) * gamma * act(gate)``, one scale of a head's width."""
    rs = np.random.RandomState(6)
    x, z, gamma = _rand(rs, 2, 5, 24), _rand(rs, 2, 5, 24), _rand(rs, 8) + 1
    fn = {"sigmoid": jax.nn.sigmoid, "silu": jax.nn.silu}[act]

    def want(x, z, gamma):
        y = x.reshape(2, 5, 3, 8)
        y = y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True) + 1e-5)
        return (y * gamma).reshape(2, 5, 24) * fn(z)

    def op(x, z, gamma):
        return lm.gated_rms_norm(x, z, gamma, group_size=8, gate_act=act,
                                 norm_first=True)

    _check(op, want, (x, z, gamma))
    sym = mx.sym._contrib_GatedRMSNorm(
        mx.sym.Variable("x"), mx.sym.Variable("z"), name="n", group_size=8,
        gate_act=act, norm_first=True)
    shapes, _, _ = sym.infer_shape(x=(2, 5, 24), z=(2, 5, 24))
    assert dict(zip(sym.list_arguments(), shapes))["n_gamma"] == (8,)
    # no group: the statistics and the scale over all of the features
    whole = mx.sym._contrib_GatedRMSNorm(
        mx.sym.Variable("x"), mx.sym.Variable("z"), name="n", gate_act=act,
        norm_first=True)
    shapes, _, _ = whole.infer_shape(x=(2, 5, 24), z=(2, 5, 24))
    assert dict(zip(whole.list_arguments(), shapes))["n_gamma"] == (24,)


# -- latent attention: keys wider than values ---------------------------------

def _plain_attention(q, k, v, heads):
    b, t, _ = q.shape
    qh, kh, vh = (x.reshape(b, t, heads, -1) for x in (q, k, v))
    s = jnp.einsum("bqhd,bkhd->bhqk", qh, kh) / math.sqrt(qh.shape[-1])
    keep = jnp.tril(jnp.ones((t, t), bool))
    p = jax.nn.softmax(jnp.where(keep, s, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, vh).reshape(b, t, -1)


@pytest.mark.parametrize("t,dqk,dv", [(24, 12, 8), (37, 24, 16), (16, 8, 12)],
                         ids=lambda v: str(v))
def test_causal_attention_with_a_value_width_of_its_own(t, dqk, dv):
    """``D_qk`` != ``D_v`` through the op, forward and the three gradients,
    against plain softmax attention; scores over ``sqrt(D_qk)``."""
    rs = np.random.RandomState(t)
    heads = 2
    args = (_rand(rs, 2, t, heads * dqk), _rand(rs, 2, t, heads * dqk),
            _rand(rs, 2, t, heads * dv))

    def op(q, k, v):
        return lm.causal_attention(q, k, v, num_heads=heads)

    assert op(*args).shape == (2, t, heads * dv)
    _check(op, lambda *a: _plain_attention(*a, heads), args, tol=5e-5)


# -- the share ties to the model ----------------------------------------------

def test_four_shares_add_up_to_the_whole_layer():
    """16 experts in 4 shares of 4: the four shares' routed parts plus the
    shared expert counted once give the uncut reference's feed-forward."""
    rs = np.random.RandomState(7)
    d, f, n = 32, 16, 16
    c = dict(CONFIG, num_experts=n, num_experts_per_tok=4, experts_held=None,
             layer_types=["kda", "kda"], num_dense_layers=0)
    p = {"l1_moe_router_weight": _rand(rs, n, d, scale=0.3),
         "l1_moe_expert_w1_weight": _rand(rs, n, d, f, scale=0.2),
         "l1_moe_expert_w3_weight": _rand(rs, n, d, f, scale=0.2),
         "l1_moe_expert_w2_weight": _rand(rs, n, f, d, scale=0.2)}
    shared = {"l1_shared_w%d_weight" % i: _rand(rs, *s, scale=0.2)
              for i, s in ((1, (f, d)), (3, (f, d)), (2, (d, f)))}
    x, bias = _rand(rs, 30, d), _rand(rs, n, scale=0.05)
    with jax.default_matmul_precision("highest"):
        whole, _ = ref.routed(x, p, bias, "l1_moe", c, None)
        once = ref.swiglu(x, shared, "l1_shared", None)
    total = once
    for first in range(0, n, 4):
        held = slice(first, first + 4)
        part, counts = moe.moe_layer(
            x, p["l1_moe_router_weight"], bias,
            p["l1_moe_expert_w1_weight"][held],
            p["l1_moe_expert_w3_weight"][held],
            p["l1_moe_expert_w2_weight"][held], top_k=4,
            experts_held=(first, 4), route_scale=2.446)
        assert int(counts.sum()) == 30 * 4      # every share routes alike
        total = total + part
    _close(total, whole + once, tol=1e-4)


# -- the symbol ---------------------------------------------------------------

def test_leaf_types_follow_the_compute_type():
    """Without shapes, as the benchmark's window asks, every leaf's type is
    the one the Module binds: matrices and taps in the compute type, the
    norms' scales and the offsets of ``A_log`` and ``dt_bias`` float32,
    whatever float32 value (a start by index) enters a layer before them."""
    sym = kimi_linear.get_symbol(dtype="bfloat16", **CONFIG)
    types, _, aux = sym.infer_type(data=np.int32, label=np.float32)
    asked = dict(zip(sym.list_arguments(), types))
    for name, t in asked.items():
        if name in ("data", "label"):
            continue
        f32 = name.endswith(("_gamma", "_A_log_offset", "_dt_bias_offset"))
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
    prog = _GraphProgram(kimi_linear.get_symbol(dtype="float32", **CONFIG))
    marked = [n.name for n in prog.nodes
              if n.op is not None and MIRROR_STAGE in n._extra_attrs]
    assert marked == [p + s for p in ("l0_", "l1_", "l2_", "l3_")
                      for s in ("attn_norm", "ffn_norm")] + ["final_norm"]
    names = {n.name for n in prog.nodes if n.op is not None}
    assert {"l0_kda_conv_q", "l0_kda_conv_k", "l0_kda_conv_v", "l0_kda_core",
            "l0_kda_norm", "l0_ffn_w1", "l1_moe", "l1_shared_w1",
            "l2_attn_core", "l2_attn_kva_norm"} <= names
    sym = kimi_linear.get_symbol(dtype="float32", **CONFIG)
    shapes = dict(zip(sym.list_arguments(),
                      sym.infer_shape(data=(1, 16), label=(1, 16))[0]))
    assert shapes["l2_attn_wq_weight"] == (2 * 12, 32)
    assert shapes["l2_attn_wkva_weight"] == (12 + 4, 32)
    assert shapes["l2_attn_wkvb_weight"] == (2 * 16, 12)
    assert shapes["l0_kda_norm_gamma"] == (16,)
    assert shapes["l0_kda_dt_bias_offset"] == (32,)
    assert shapes["l0_kda_conv_q_weight_offset"] == (32, 4)


def test_kda_starts_by_index():
    """``A`` over 1..16 by head, a head's steps over 0.001..0.1 by channel,
    the taps over +-0.5: symbol and reference alike."""
    a_log, dt_bias = ref.kda_start(dict(kda_num_heads=4, kda_head_dim=16))
    np.testing.assert_allclose(np.exp(a_log), [1, 6, 11, 16], rtol=1e-6)
    step = np.asarray(jax.nn.softplus(dt_bias)).reshape(4, 16)
    assert step.min() > 0.001 and step.max() < 0.1
    assert step.max(axis=1).min() / step.min(axis=1).max() > 50
    taps = np.asarray(ref.conv_start(64, 4))
    assert -0.5 < taps.min() < -0.49 and 0.49 < taps.max() < 0.5


# -- the whole model through Module.fit ---------------------------------------

def _fit_three_steps():
    from mxnet_tpu.io import DataBatch, DataDesc, DataIter
    B, T = 2, 20            # T is no multiple of the chunk (16)
    sym = kimi_linear.get_symbol(dtype="float32", **CONFIG)
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


@pytest.fixture(scope="module")
def fitted():
    from mxnet_tpu import telemetry
    before = dict(telemetry.counters())
    return _fit_three_steps() + (before, dict(telemetry.counters()))


def test_module_fit_matches_reference(fitted):
    """Three steps of ``Module.fit`` (one fused dispatch a batch, Adam,
    the bias written inside the step) against the reference: loss, first
    gradient, parameter change, bias change."""
    mod, params, aux, batches, losses, first, before, now = fitted
    assert mod._fused_fallback_reason is None
    assert now["dispatch.train_step"] \
        - before.get("dispatch.train_step", 0) == 3
    want = ref.run_steps(params, aux, batches, 1e-3, 0.0, 0.0, config=CONFIG,
                         beta1=0.9, beta2=0.95, epsilon=1e-8)
    np.testing.assert_allclose(losses, want["losses"], rtol=2e-5)
    arg, auxp = mod.get_params()
    floor = 1e-3 * float(np.median(list(want["grad_norms"].values())))
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


def test_fit_publishes_kda_and_moe_counters(fitted):
    """Three KDA layers and three expert layers, three steps: ``kda.steps``
    9 and ``kda.chunks_run`` 9 x 2 sequences x ceil(20 / 16) chunks, beside
    the routed layers' ``moe.*``; the op holds no auxiliary state."""
    mod, *_, before, now = fitted

    def grew(k):
        return now.get(k, 0) - before.get(k, 0)

    assert grew("kda.steps") == 3 * 3
    assert grew("kda.chunks_run") == 3 * 3 * 2 * 2
    assert grew("moe.steps") == 3 * 3
    _, auxp = mod.get_params()
    assert not [k for k in auxp if "kda" in k]


# -- the reference's planted faults -------------------------------------------

@pytest.fixture(scope="module")
def sound(fitted):
    _, params, aux, batches, *_ = fitted
    kw = dict(config=CONFIG, beta1=0.9, beta2=0.95, epsilon=1e-8)
    return (params, aux, batches, kw,
            ref.run_steps(params, aux, batches, 1e-3, 0.0, 0.0, **kw))


def _caught(got, want):
    with open(os.path.join(ROOT, "benchmarks", "limits",
                           "kimi_linear.fit.json")) as f:
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
