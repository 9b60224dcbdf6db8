"""The Mamba-2 mixer's ops (``_contrib_SSD``, ``_contrib_CausalConv1D``,
``_contrib_GatedRMSNorm``), the ungated expert layer
(``_contrib_MoEUngated``) and the ``nemotron_h`` symbol, each against the
plain reference ``benchmarks/reference/nemotron_h.py`` (float32, small
sizes, seeded random weights)."""
import importlib.util
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu.ops import lm, get_op
from mxnet_tpu.parallel import moe

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(path, name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(ROOT, path))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load("benchmarks/reference/nemotron_h.py", "nemotron_h_reference")
nemotron_h = _load("examples/language-model/symbols/nemotron_h.py",
                   "nemotron_h_symbol")
correct = _load("benchmarks/harness/correct.py", "bench_correct")

CONFIG = dict(
    hidden_size=32, num_attention_heads=4, num_key_value_heads=2, head_dim=8,
    layer_types=["mamba", "moe", "mamba", "attention", "moe"],
    num_hidden_layers=5, mamba_num_heads=4, mamba_head_dim=8,
    ssm_state_size=16, n_groups=2, conv_kernel=4, chunk_size=8,
    time_step_min=0.001, time_step_max=0.1, num_experts=8,
    num_experts_per_tok=2, moe_intermediate_size=16, num_shared_experts=1,
    moe_shared_expert_intermediate_size=24, score_func="sigmoid",
    route_norm=True, route_scale=2.5, load_balance_coeff=0.001,
    rms_norm_eps=1e-5, vocab_size=40, experts_held=[2, 4])
H, P, N, G = 4, 8, 16, 2


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
        _close(g, e, tol * max(1.0, float(jnp.max(jnp.abs(e)))))


# -- the mixer's ops ----------------------------------------------------------

def _recurrence(x, dt, b, c, a_log, dt_bias, d, dims=(H, P, N, G),
                rounded=None):
    """The reference's token-by-token scan, a sequence at a time, in
    float32. ``rounded``: the type the configuration rounds the products'
    operand ``delta x`` to (the inputs are then of that type too)."""
    h, p, n, g = dims
    x, dt, b, c = (v.astype(jnp.float32) for v in (x, dt, b, c))

    def one(x, dt, b, c):
        t = x.shape[0]
        delta = jax.nn.softplus(dt + dt_bias)
        xh = x.reshape(t, g, h // g, p)
        xd = xh * delta.reshape(t, g, h // g, 1)
        if rounded is not None:
            xd = xd.astype(rounded).astype(jnp.float32)
        y = ref.recurrence(
            xd, jnp.exp(-jnp.exp(a_log) * delta).reshape(t, g, h // g),
            b.reshape(t, g, n), c.reshape(t, g, n),
            block=4 if t % 4 == 0 else t)
        return (y + d.reshape(g, h // g, 1) * xh).reshape(t, h * p)
    return jnp.stack([one(*s) for s in zip(x, dt, b, c)])


def _ssd_args(rs, batch, t, dims, dtype=jnp.float32):
    h, p, n, g = dims
    shapes = ((batch, t, h * p), (batch, t, h), (batch, t, g * n),
              (batch, t, g * n), (h,), (h,), (h,))
    return tuple(_rand(rs, *s).astype(dtype if i < 4 else jnp.float32)
                 for i, s in enumerate(shapes))


@pytest.mark.parametrize("t,chunk", [(37, 8), (37, 16), (32, 8), (5, 128)],
                         ids=lambda v: str(v))
def test_ssd_matches_the_token_recurrence(t, chunk):
    """Forward and every input's gradient, at a T that is and is not a
    multiple of the chunk, at two chunk sizes, and at a chunk longer than
    the sequence."""
    args = _ssd_args(np.random.RandomState(0), 2, t, (H, P, N, G))
    _check(lambda *a: lm.ssd(*a, heads=H, head_dim=P, state=N, groups=G,
                             chunk=chunk), _recurrence, args)


TILE = (2, 64, 128, 1)      # heads, head_dim, state, groups: the kernels' own


def _worst(got, want):
    """The largest distance over the largest value of ``want``."""
    got, want = (np.asarray(v, np.float32) for v in (got, want))
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


def check_ssd_at_the_tile(t, dtype, dims=TILE, chunk=128):
    """``lm.ssd`` at the kernels' tile against the token recurrence with
    the configuration's roundings (``delta x`` in the data's type, decays
    and states float32), forward and every input's gradient: to float32's
    last places in float32, to bfloat16's (``M``, the states and the
    cotangents are rounded once each) in bfloat16. Also what the chip's
    lane runs (``tests/tpu``)."""
    h, p, n, g = dims
    args = _ssd_args(np.random.RandomState(5), 1, t, dims, dtype)
    half = dtype != jnp.float32
    tol = 2e-2 if half else 2e-5

    def op(*a):
        return lm.ssd(*a, heads=h, head_dim=p, state=n, groups=g, chunk=chunk)

    def want(*a):
        return _recurrence(*a, dims=dims, rounded=dtype if half else None)

    w = jnp.cos(jnp.arange(t * h * p, dtype=jnp.float32)).reshape(1, t, -1)
    out, pull = jax.vjp(op, *args)
    exp, pull_exp = jax.vjp(want, *args)
    assert out.dtype == dtype
    assert _worst(out, exp) < tol
    for got, e in zip(pull(w.astype(dtype)), pull_exp(w)):
        assert got.dtype == e.dtype
        assert _worst(got, e) < tol, (got.shape, _worst(got, e))


@pytest.mark.parametrize("t", [256, 300])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_ssd_matches_the_token_recurrence_at_the_kernels_tile(t, dtype):
    """Chunks of 128, heads of 64, state 128, two heads in one group: no
    padding inside the op, at a T that is and is not whole chunks."""
    check_ssd_at_the_tile(t, dtype)


def test_a_mirrored_segment_does_not_run_the_chunk_kernel_again():
    """Under the step's checkpoint policy the gradient of a node holds one
    ``ssd_chunk_fwd`` call and one ``ssd_chunk_bwd`` call: the backward's
    residuals are the op's inputs, so the recomputed segment has no use of
    its own for the forward kernel's output. The states' kernel runs
    again: the entering states are an input of the backward kernel."""
    from mxnet_tpu import executor
    args = _ssd_args(np.random.RandomState(6), 1, 32, (H, P, N, G))

    def op(*a):
        return lm.ssd(*a, heads=H, head_dim=P, state=N, groups=G, chunk=8)

    def loss(*a):
        return jnp.sum(jax.checkpoint(op, policy=executor._MIRROR_POLICY)(*a))

    text = str(jax.make_jaxpr(jax.grad(loss, tuple(range(7))))(*args))
    assert text.count("name=ssd_chunk_fwd") == 1, text.count("ssd_chunk_fwd")
    assert text.count("name=ssd_chunk_bwd") == 1
    assert text.count("name=ssd_state_fwd") == 2
    assert text.count("name=ssd_state_bwd") == 1


def test_ssd_counts_its_chunks():
    """A training step's chunks are fixed by the shapes: counted on the
    host, and the op holds no state."""
    from mxnet_tpu import telemetry
    op = get_op("_contrib_SSD")
    before = dict(telemetry.counters())
    op.step_counters((2, 37, H * P), dict(chunk=8), 3)
    now = telemetry.counters()
    assert now["ssm.steps"] - before.get("ssm.steps", 0) == 3
    assert now["ssm.chunks_run"] - before.get("ssm.chunks_run", 0) \
        == 3 * 2 * 5
    assert not op.aux_inputs and op.stateful_update is None


def test_causal_conv1d():
    rs = np.random.RandomState(1)
    x, w, b = _rand(rs, 2, 11, 6), _rand(rs, 6, 4), _rand(rs, 6)

    def want(x, w, b):      # XLA's grouped convolution, one channel a group
        y = jax.lax.conv_general_dilated(
            x, w.T[:, None, :], (1,), [(3, 0)],
            dimension_numbers=("NWC", "WIO", "NWC"), feature_group_count=6)
        return jax.nn.silu(y + b)

    _check(lm.causal_conv1d, want, (x, w, b))
    # causal: position t reads nothing after t
    later = x.at[:, 7:].set(0.0)
    _close(lm.causal_conv1d(later, w, b)[:, :7],
           lm.causal_conv1d(x, w, b)[:, :7])
    _close(lm.causal_conv1d(x, w, b, act_type=None),
           jax.lax.conv_general_dilated(
               x, w.T[:, None, :], (1,), [(3, 0)],
               dimension_numbers=("NWC", "WIO", "NWC"),
               feature_group_count=6) + b)


@pytest.mark.parametrize("group", [0, 8])
def test_gated_rms_norm(group):
    rs = np.random.RandomState(2)
    x, z, g = _rand(rs, 2, 5, 32), _rand(rs, 2, 5, 32), 1 + _rand(
        rs, 32, scale=0.1)

    def want(x, z, g):
        y = (x * jax.nn.silu(z)).reshape(2, 5, -1, group or 32)
        return ref.rms(y, g.reshape(-1, group or 32), 1e-5).reshape(x.shape)

    _check(lambda x, z, g: lm.gated_rms_norm(x, z, g, 1e-5, group), want,
           (x, z, g))


# -- the ungated experts ------------------------------------------------------

def _moe_params(rs, n, d, f, count):
    return dict(router=_rand(rs, n, d, scale=0.5),
                w1=_rand(rs, count, d, f, scale=0.2),
                w2=_rand(rs, count, f, d, scale=0.2))


def _ref_moe(x, p, bias, c):
    named = {"m_router_weight": p["router"], "m_expert_w1_weight": p["w1"],
             "m_expert_w2_weight": p["w2"]}
    return ref.routed(x, named, bias, "m", c, None)


def test_ungated_moe_op_matches_the_dense_loop():
    rs = np.random.RandomState(4)
    c, d, f = CONFIG, 32, 16
    p = _moe_params(rs, 8, d, f, 4)
    x, bias = _rand(rs, 24, d), _rand(rs, 8, scale=0.05)
    kw = dict(num_experts=8, top_k=2, hidden=f,
              experts_held=tuple(c["experts_held"]), route_scale=2.5)

    def got(x, router, w1, w2):
        return lm.moe_ungated(x, router, w1, w2, bias, jnp.zeros(5), **kw)[0]

    def want(x, router, w1, w2):
        return _ref_moe(x, dict(router=router, w1=w1, w2=w2), bias, c)[0]

    _check(got, want, (x, p["router"], p["w1"], p["w2"]), tol=1e-4)
    counts = lm.moe_ungated(x, p["router"], p["w1"], p["w2"], bias,
                            jnp.zeros(5), **kw)[1]
    _close(counts, _ref_moe(x, p, bias, c)[1])
    # relu^2 is not relu: the planted fault's form reads differently
    plain = lm.moe_ungated(x, p["router"], p["w1"], p["w2"], bias,
                           jnp.zeros(5), act="relu", **kw)[0]
    assert float(jnp.max(jnp.abs(plain - got(x, p["router"], p["w1"],
                                             p["w2"])))) > 1e-3
    # the state a training step writes, at the ungated op's own indices
    op = get_op("_contrib_MoEUngated")
    assert op.aux_inputs == (4, 5) and op.visible_outputs == 1
    new = op.stateful_update(
        [x, p["router"], p["w1"], p["w2"], bias, jnp.zeros(5)],
        (None, counts), dict(kw, _train=True, load_balance_coeff=0.001))
    _close(new[4], ref.bias_update(bias, counts.astype(jnp.float32), 0.001))
    rows = np.asarray(counts)[2:6]
    _close(new[5], [1, rows.sum(), rows.max(), 1, 0])
    # the gated op keeps its own
    assert get_op("_contrib_MoE").aux_inputs == (5, 6)


def test_sixteen_shares_add_up_to_the_whole_layer():
    """The sixteen shares' routed parts (each chip's two held experts of
    32, top-6) plus the shared expert counted once give the uncut
    reference's expert block."""
    rs = np.random.RandomState(7)
    d, f, n = 32, 16, 32
    c = dict(CONFIG, num_experts=n, num_experts_per_tok=6, experts_held=None)
    p = _moe_params(rs, n, d, f, n)
    x, bias = _rand(rs, 30, d), _rand(rs, n, scale=0.05)
    named = {"l_moe_router_weight": p["router"],
             "l_moe_expert_w1_weight": p["w1"],
             "l_moe_expert_w2_weight": p["w2"],
             "l_shared_w1_weight": _rand(rs, 24, d, scale=0.2),
             "l_shared_w2_weight": _rand(rs, d, 24, scale=0.2)}
    whole, _ = ref.experts_block(x, named, bias, "l_", c, None, None)
    total = jnp.square(jax.nn.relu(x @ named["l_shared_w1_weight"].T)) \
        @ named["l_shared_w2_weight"].T
    for first in range(0, n, 2):
        part, counts = moe.moe_layer(
            x, p["router"], bias, p["w1"][first:first + 2], None,
            p["w2"][first:first + 2], top_k=6, experts_held=(first, 2),
            route_scale=2.5, act="relu2")
        assert int(counts.sum()) == 30 * 6      # every share routes alike
        total = total + part
    _close(total, whole, tol=1e-4)


# -- the symbol ---------------------------------------------------------------

def test_blocks_from_a_pattern_string_or_a_list():
    kinds = nemotron_h.block_kinds
    assert kinds("MEMEM*EME") == [
        "mamba", "moe", "mamba", "moe", "mamba", "attention", "moe", "mamba",
        "moe"]
    assert kinds("MEMEM*EMEMEM*", 3) == ["mamba", "moe", "mamba"]
    assert kinds(["moe", "attention"]) == ["moe", "attention"]
    by_string = nemotron_h.get_symbol(dtype="float32", **dict(
        CONFIG, layer_types="MEM*E"))
    by_list = nemotron_h.get_symbol(dtype="float32", **CONFIG)
    assert by_string.list_arguments() == by_list.list_arguments()


def test_mamba_starts_where_mamba2_starts():
    """With every offset nought the symbol's ``A_log`` and ``dt_bias`` are
    Mamba-2's starting values: ``A`` from 1 to 16 and ``softplus(dt_bias)``
    from ``time_step_min`` to ``time_step_max``, by head index, as the
    reference has them."""
    c = dict(CONFIG, mamba_num_heads=6)
    a_log, dt_bias = nemotron_h._mamba_start("m_", c)
    both = mx.sym.Group([a_log, dt_bias])
    ex = both.bind(mx.cpu(), {k: mx.nd.zeros((6,))
                              for k in both.list_arguments()})
    a, b = (o.asnumpy() for o in ex.forward())
    want_a, want_b = ref.mamba_start(c)
    _close(a, want_a, 1e-6)
    _close(b, want_b, 1e-5)
    _close(np.exp(a), np.linspace(1, 16, 6), 1e-5)
    delta = np.log1p(np.exp(b))
    _close(delta[[0, -1]], [0.001, 0.1], 1e-5)
    assert np.all(np.diff(np.log(delta)) > 0)
    assert both.list_arguments() == ["m_A_log_offset", "m_dt_bias_offset"]


def test_leaf_types_follow_the_compute_type():
    """Without shapes, as the benchmark's window asks: matrices, taps and
    the convolution's bias in the compute type, the norms' scales, ``D`` and
    the offsets of ``A_log`` and ``dt_bias`` float32, whatever float32 value
    (the taps' start) enters a block before them."""
    sym = nemotron_h.get_symbol(dtype="bfloat16", **CONFIG)
    types, _, aux = sym.infer_type(data=np.int32, label=np.float32)
    for name, t in zip(sym.list_arguments(), types):
        if name in ("data", "label"):
            continue
        f32 = name.endswith(("_gamma", "_A_log_offset", "_dt_bias_offset"))
        assert str(np.dtype(t)) == ("float32" if f32 else "bfloat16"), name
    assert all(np.dtype(t) == np.float32 for t in aux)


def test_mirror_stages_cut_one_segment_a_block():
    from mxnet_tpu.executor import _GraphProgram, MIRROR_STAGE
    prog = _GraphProgram(nemotron_h.get_symbol(dtype="float32", **CONFIG))
    assert prog.mirror_stages
    marked = [n.name for n in prog.nodes
              if n.op is not None and MIRROR_STAGE in n._extra_attrs]
    assert marked == ["l0_norm", "l1_norm", "l2_norm", "l3_norm", "l4_norm",
                      "final_norm"]
    names = {n.name for n in prog.nodes if n.op is not None}
    assert {"l0_mixer_conv", "l0_mixer_ssd", "l0_mixer_norm", "l1_moe",
            "l1_shared_w1", "l3_attn_core"} <= names


# -- the whole model through Module.fit ---------------------------------------

def _fit_three_steps():
    from mxnet_tpu.io import DataBatch, DataDesc, DataIter
    B, T = 2, 20            # T is no multiple of the chunk (8)
    sym = nemotron_h.get_symbol(dtype="float32", **CONFIG)
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
    assert sorted(want["aux_change_norms"]) == ["l1_moe_bias", "l4_moe_bias"]
    for k, v in want["aux_change_norms"].items():
        assert v > 0
        change = float(np.linalg.norm(auxp[k].asnumpy() - aux[k]))
        assert abs(change - v) <= 1e-4 * v, k


def test_fit_publishes_ssm_and_moe_counters(fitted):
    """Two mixers and two expert layers, three steps: ``ssm.steps`` 6 and
    ``ssm.chunks_run`` 6 x 2 sequences x ceil(20 / 8) chunks, beside the
    ungated layers' ``moe.*``."""
    mod, *_, before, now = fitted

    def grew(k):
        return now.get(k, 0) - before.get(k, 0)

    assert grew("ssm.steps") == 2 * 3
    assert grew("ssm.chunks_run") == 2 * 3 * 2 * 3
    assert grew("moe.steps") == 2 * 3 and grew("moe.chunks_run") == 2 * 3
    assert 0 < grew("moe.rows_max") <= grew("moe.rows_held") <= 6 * 2 * 20 * 2
    _, auxp = mod.get_params()
    assert not [k for k in auxp if "ssd" in k]


# -- the reference's planted faults -------------------------------------------

@pytest.fixture(scope="module")
def sound(fitted):
    _, params, aux, batches, *_ = fitted
    kw = dict(config=CONFIG, beta1=0.9, beta2=0.95, epsilon=1e-8)
    return (params, aux, batches, kw,
            ref.run_steps(params, aux, batches, 1e-3, 0.0, 0.0, **kw))


def _limits():
    import json
    with open(os.path.join(ROOT, "benchmarks", "limits",
                           "nemotron3_nano.fit.json")) as f:
        return json.load(f)["rehearse"]


def _caught(got, want):
    ok, table = correct.judge(correct.compare(got, want), _limits())
    return not ok, table


@pytest.mark.parametrize("fault", [f for f in ref.FAULTS + ("control",)
                                   if f != "bf16_decay"])
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


def test_bfloat16_decays_are_caught_over_a_long_sequence():
    """A slow head's decay (0.999 a token) rounds to 1 in bfloat16: nothing
    a sequence of 20 tokens shows, and over the limits after 512."""
    assert "bf16_decay" in ref.FAULTS
    t = 512
    c = dict(CONFIG, layer_types=["mamba", "moe", "mamba"],
             num_hidden_layers=3)
    sym = nemotron_h.get_symbol(dtype="float32", **c)
    shapes, _, aux_shapes = sym.infer_shape(data=(1, t), label=(1, t))
    rs = np.random.RandomState(3)
    params = {n: (np.ones(s) if n.endswith("gamma")
                  else rs.randn(*s) * 0.02).astype(np.float32)
              for n, s in zip(sym.list_arguments(), shapes)
              if n not in ("data", "label")}
    aux = {n: np.zeros(s, np.float32)
           for n, s in zip(sym.list_auxiliary_states(), aux_shapes)}
    ids = rs.randint(0, 40, (2, 1, t + 1))
    batches = [(b[:, :-1].astype(np.int32), b[:, 1:].astype(np.float32))
               for b in ids]
    kw = dict(config=c, beta1=0.9, beta2=0.95, epsilon=1e-8)
    want = ref.run_steps(params, aux, batches, 1e-3, 0.0, 0.0, **kw)
    got = ref.run_steps(params, aux, batches, 1e-3, 0.0, 0.0,
                        fault="bf16_decay", **kw)
    caught, table = _caught(got, want)
    assert caught and table["param_change.total"]["value"] > 5e-4, table
