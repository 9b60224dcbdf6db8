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
        return lm.moe(x, router, w1, w3, w2, bias, jnp.zeros(3), **kw)[0]

    def want(x, router, w1, w3, w2):
        return _ref_moe(x, dict(router=router, w1=w1, w3=w3, w2=w2), bias,
                        c)[0]

    _check(got, want, (x, p["router"], p["w1"], p["w3"], p["w2"]), tol=1e-4)
    counts = lm.moe(x, p["router"], p["w1"], p["w3"], p["w2"], bias,
                    jnp.zeros(3), **kw)[1]
    _close(counts, _ref_moe(x, p, bias, c)[1])
    # the state a training step writes: the bias by the rule, the load
    new = get_op("_contrib_MoE").stateful_update(
        [x, p["router"], p["w1"], p["w3"], p["w2"], bias, jnp.zeros(3)],
        (None, counts), dict(kw, _train=True, load_balance_coeff=0.001))
    _close(new[5], ref.bias_update(bias, counts.astype(jnp.float32), 0.001))
    rows = np.asarray(counts)[2:6]
    _close(new[6], [1, rows.sum(), rows.max()])


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
    (2, 2, 19, 19, True, 1, 8, None)],
    ids=lambda c: "hq%d_hkv%d_s%dx%d_c%d_w%s_b%sx%s" % c)
def test_flash_kernel_matches_plain(case):
    hq, hkv, s_q, s_kv, causal, window, bq, bk = case
    rs = np.random.RandomState(6)
    q, k, v = (_rand(rs, 2, hq, s_q, 16), _rand(rs, 2, hkv, s_kv, 16),
               _rand(rs, 2, hkv, s_kv, 16))

    def plain(q, k, v):
        g = hq // hkv
        kk, vv = jnp.repeat(k, g, axis=1), jnp.repeat(v, g, axis=1)
        s = jnp.einsum("bhqd,bhkd->bhqk", q, kk) / 4.0
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
