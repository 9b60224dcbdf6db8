"""Partition-rule sharding engine (ISSUE 15): ONE declarative spec for
dp x mp meshes, shared by training and serving.

Pinned properties:

1. RULE TREE — ordered (regex, PartitionSpec) pairs, first match wins,
   scalars never shard, explicit UNMATCHED policy (replicate or error),
   matched-but-nondivisible specs downgrade to replicate (warned +
   counted, never silent).
2. TRAINING — a rules-sharded Module on the 2x4 (and 4x2) dp x mp CPU
   mesh runs the whole train step as ONE fused dispatch per batch,
   BIT-equal to the same-mesh phase-split oracle and matching the
   single-device fused oracle at the reassociation noise floor
   (rtol 1e-5); the buffer ledger's committed ``param`` bytes show the
   1/mp per-device saving.
3. CHECKPOINTS — save gathers per-shard to ONE host file with the
   layout in meta; restore re-shards onto whatever mesh the resuming
   process binds (dp-only ckpt -> dp x mp and vice versa), including
   optimizer state re-committed to the weight's RULE-derived placement
   (the ``Updater._sync_state`` regression).
4. SERVING — ``InferenceEngine(partition_rules=...)`` serves with
   mp-sharded device-resident params, equal to the replicated path as
   far as two programs can be (``helpers.assert_equal_across_shapes``).
5. ERRORS — batch divisibility on a 2-D mesh is checked (and reported)
   against the ``dp`` AXIS, not the device count.
"""
import contextlib
import gc
import os

import numpy as np
import pytest

import jax
from jax.sharding import PartitionSpec as P

import mxnet_tpu as mx
from helpers import assert_equal_across_shapes
from mxnet_tpu import nd, sym, telemetry
from mxnet_tpu.base import MXNetError
from mxnet_tpu.io import DataBatch, DataDesc
from mxnet_tpu.parallel import (PartitionRules, mesh_from_contexts,
                                rule_spec)
from mxnet_tpu.parallel import spmd as _spmd
from mxnet_tpu.parallel.partition import (committed_nbytes,
                                          partition_summary)

N_DEV = min(8, jax.device_count())

needs_mesh = pytest.mark.skipif(
    N_DEV < 8, reason="needs the 8-device virtual CPU mesh")

RULES = PartitionRules([
    (r"fc\d+_weight$", P("mp", None)),
    (r"fc\d+_bias$", P("mp")),
])


@contextlib.contextmanager
def _pin(value):
    old = os.environ.get("MXNET_MODULE_FUSED_STEP")
    os.environ["MXNET_MODULE_FUSED_STEP"] = value
    try:
        yield
    finally:
        if old is None:
            del os.environ["MXNET_MODULE_FUSED_STEP"]
        else:
            os.environ["MXNET_MODULE_FUSED_STEP"] = old


# ---------------------------------------------------------------------------
# 1. Rule-tree matching
# ---------------------------------------------------------------------------

def test_first_match_wins_in_order():
    rules = PartitionRules([
        (r"weight", P("mp", None)),
        (r"fc1_weight", P(None, "mp")),   # unreachable: later in order
        (r".*", P()),
    ])
    assert tuple(rules.spec_for("fc1_weight", (8, 8))) == ("mp", None)
    # order is the spec: reversing the rules flips the winner
    flipped = PartitionRules([
        (r"fc1_weight", P(None, "mp")),
        (r"weight", P("mp", None)),
    ])
    assert tuple(flipped.spec_for("fc1_weight", (8, 8))) == (None, "mp")


def test_scalars_never_shard():
    rules = PartitionRules([(r".*", P("mp"))])
    assert tuple(rules.spec_for("gamma", ())) == ()
    assert tuple(rules.spec_for("beta", (1,))) == ()
    assert tuple(rules.spec_for("w", (8,))) == ("mp",)


def test_unmatched_replicate_default():
    assert tuple(RULES.spec_for("bn_gamma", (32,))) == ()


def test_unmatched_error_policy():
    rules = PartitionRules([(r"weight$", P("mp"))], unmatched="error")
    assert tuple(rules.spec_for("a_weight", (8,))) == ("mp",)
    with pytest.raises(MXNetError, match="no rule matches"):
        rules.spec_for("stray_bias", (8,))


def test_bad_policy_and_bad_rule_rejected():
    with pytest.raises(MXNetError, match="unmatched policy"):
        PartitionRules([], unmatched="ignore")
    with pytest.raises(MXNetError, match="pattern, spec"):
        PartitionRules(["not-a-pair"])


def test_apply_maps_shapes_and_arrays():
    rules = PartitionRules([(r"w$", P("mp", None))])
    out = rules.apply({"w": np.zeros((8, 4)), "b": (4,), "s": ()})
    assert tuple(out["w"]) == ("mp", None)
    assert tuple(out["b"]) == ()
    assert tuple(out["s"]) == ()


def test_rules_hashable_and_eq():
    a = PartitionRules([(r"w$", P("mp"))])
    b = PartitionRules([(r"w$", P("mp"))])
    c = PartitionRules([(r"w$", P("mp"))], unmatched="error")
    assert a == b and hash(a) == hash(b)
    assert a != c


@needs_mesh
def test_nondivisible_matched_spec_downgrades_with_counter():
    contexts = [mx.cpu(i) for i in range(8)]
    mesh = mesh_from_contexts(contexts, axes={"dp": 2, "mp": 4})
    spec = rule_spec(mesh, PartitionRules([(r".*", P("mp"))]))
    was = telemetry.enabled()
    telemetry.enable()
    try:
        telemetry.reset()
        sh = spec.param_sharding("odd", (6,))     # 6 % 4 != 0
        assert tuple(sh.spec) == ()
        assert telemetry.counters().get(
            "partition.replicated_fallback", 0) >= 1
        # an unknown axis downgrades the same way
        spec2 = rule_spec(mesh, PartitionRules([(r".*", P("tp"))]))
        assert tuple(spec2.param_sharding("w", (8,)).spec) == ()
    finally:
        if not was:
            telemetry.disable()


@needs_mesh
def test_mesh_from_contexts_axes_form():
    contexts = [mx.cpu(i) for i in range(8)]
    mesh = mesh_from_contexts(contexts, axes={"dp": 2, "mp": -1})
    assert dict(mesh.shape) == {"dp": 2, "mp": 4}
    with pytest.raises(MXNetError, match="need 6 devices"):
        mesh_from_contexts(contexts, axes={"dp": 2, "mp": 3})
    with pytest.raises(MXNetError, match="at most one"):
        mesh_from_contexts(contexts, axes={"dp": -1, "mp": -1})


@needs_mesh
def test_batch_divisibility_error_names_the_axis():
    # with a 2-D mesh, a global batch of 6 IS divisible by dp=2 even
    # though it is not divisible by the 8 devices — and the failing
    # case must name the axis, not the device count
    contexts = [mx.cpu(i) for i in range(8)]
    mod = mx.mod.Module(_mlp(), context=contexts, partition_rules=RULES,
                        mesh_axes={"dp": 2, "mp": 4})
    mod.bind(data_shapes=[DataDesc("data", (6, 16))],
             label_shapes=[DataDesc("softmax_label", (6,))])   # 6 % 2 == 0
    mod2 = mx.mod.Module(_mlp(), context=contexts,
                         partition_rules=RULES,
                         mesh_axes={"dp": 2, "mp": 4})
    with pytest.raises(MXNetError) as e:
        mod2.bind(data_shapes=[DataDesc("data", (7, 16))],
                  label_shapes=[DataDesc("softmax_label", (7,))])
    msg = str(e.value)
    assert "'dp' mesh axis" in msg and "size 2" in msg
    assert "8 devices" not in msg


def test_check_batch_divisible_default_message_unchanged():
    with pytest.raises(MXNetError, match="not divisible by 8 devices"):
        _spmd.check_batch_divisible(6, 8)


# ---------------------------------------------------------------------------
# 2. dp x mp fused training
# ---------------------------------------------------------------------------

def _mlp(c=4):
    net = sym.FullyConnected(sym.Variable("data"), num_hidden=64,
                             name="fc1")
    net = sym.Activation(net, act_type="relu")
    net = sym.FullyConnected(net, num_hidden=c, name="fc2")
    return sym.SoftmaxOutput(net, name="softmax")


def _batches(n, batch=32, d=16, c=4, seed=7):
    rs = np.random.RandomState(seed)
    return [DataBatch(
        data=[nd.array(rs.uniform(-1, 1, (batch, d)).astype(np.float32))],
        label=[nd.array(rs.randint(0, c, batch).astype(np.float32))],
        pad=0) for _ in range(n)]


def _make(ctx, **kw):
    mod = mx.mod.Module(_mlp(), context=ctx, **kw)
    mod.bind(data_shapes=[DataDesc("data", (32, 16))],
             label_shapes=[DataDesc("softmax_label", (32,))])
    np.random.seed(11)
    mod.init_params(mx.initializer.Xavier())
    mod.init_optimizer(kvstore="device", optimizer="sgd",
                       optimizer_params={"learning_rate": 0.05,
                                         "momentum": 0.9, "wd": 1e-4})
    return mod


def _train(fused, ctx, nbatch=6, **kw):
    import mxnet_tpu.executor as _ex
    counts = {}
    with _pin("1" if fused else "0"):
        mod = _make(ctx, **kw)
        metric = mx.metric.Accuracy()
        prev, _ex.dispatch_hook = _ex.dispatch_hook, \
            lambda k: counts.__setitem__(k, counts.get(k, 0) + 1)
        try:
            for b in _batches(nbatch):
                ok = mod._fused_batch_step(b, metric)
                if fused:
                    assert ok, mod._fused_fallback_reason
                if not ok:
                    mod.forward_backward(b)
                    mod.update()
                    mod.update_metric(metric, b.label)
        finally:
            _ex.dispatch_hook = prev
    params, _ = mod.get_params()
    return ({k: v.asnumpy() for k, v in params.items()}, counts, mod,
            metric)


@needs_mesh
@pytest.mark.parametrize("axes", [{"dp": 2, "mp": 4}, {"dp": 4, "mp": 2}])
def test_dpxmp_fused_one_dispatch_and_matches_oracles(axes):
    contexts = [mx.cpu(i) for i in range(8)]
    kw = dict(partition_rules=RULES, mesh_axes=axes)
    p_fused, counts, mod, _ = _train(True, contexts, **kw)
    # exactly ONE jitted-program dispatch per batch
    assert counts == {"train_step": 6}, counts
    # mp-sharded params really are sharded on device
    w = mod._exec.arg_dict["fc1_weight"]._data
    assert "mp" in tuple(w.sharding.spec)
    # bit-equal to the same-mesh phase-split oracle (same committed
    # placements, same kernels — reduction order identical)
    p_split, _, _, _ = _train(False, contexts, **kw)
    for k in p_fused:
        assert np.array_equal(p_fused[k], p_split[k]), k
    # matches the single-device fused oracle at the dp-reassociation
    # noise floor
    p_one, _, _, _ = _train(True, mx.cpu())
    for k in p_fused:
        assert np.allclose(p_fused[k], p_one[k], rtol=1e-5,
                           atol=1e-6), k


@needs_mesh
def test_dpxmp_ledger_param_bytes_one_over_mp():
    contexts = [mx.cpu(i) for i in range(8)]
    was = telemetry.enabled()
    telemetry.enable()
    try:
        def param_bytes(**kw):
            # collect earlier modules' parameter wrappers first: a
            # charge stays on the ledger until its wrapper is collected
            # (reset() keeps what is alive), so a neighbour's module
            # not yet collected would be counted under the same mesh key
            gc.collect()
            telemetry.reset()
            mod = _make(contexts, **kw)
            led = telemetry.ledger().get("mesh(%ddev)" % N_DEV, {})
            by_kind = led.get("by_kind", {})
            n = by_kind.get("param", 0)
            del mod
            return n
        repl = param_bytes()
        mp = param_bytes(partition_rules=RULES,
                         mesh_axes={"dp": 2, "mp": 4})
        assert repl > 0 and mp > 0
        ratio = mp / repl
        # all four tensors shard over mp=4 -> per-device (== total/8)
        # parameter bytes land at ~1/4 of the replicated layout
        assert 0.2 <= ratio <= 0.35, (mp, repl, ratio)
    finally:
        if not was:
            telemetry.disable()


@needs_mesh
def test_dpxmp_fused_plan_and_card_record_layout():
    contexts = [mx.cpu(i) for i in range(8)]
    was = telemetry.enabled()
    telemetry.enable()
    try:
        telemetry.reset()
        p, counts, mod, _ = _train(True, contexts,
                                   partition_rules=RULES,
                                   mesh_axes={"dp": 2, "mp": 4})
        plan = mod._fused_plan
        assert plan["layout"]["mesh_axes"] == {"dp": 2, "mp": 4}
        assert "fc1_weight" in \
            plan["layout"]["partition"]["sharded_params"]
        cards = [c for c in telemetry.programs().values()
                 if c.get("kind") == "train_step" and c.get("partition")]
        assert cards, "no train_step card carries the partition summary"
        part = cards[0]["partition"]
        assert part["mesh_axes"] == {"dp": 2, "mp": 4}
        assert part["sharded_params"] == 4
    finally:
        if not was:
            telemetry.disable()


@needs_mesh
def test_mesh_axes_without_rules_is_plain_dp():
    # mesh_axes={"dp": -1} with no rule tree: everything replicated,
    # fused step runs — the reshaped-mesh path is rule-free compatible
    contexts = [mx.cpu(i) for i in range(8)]
    p, counts, _, _ = _train(True, contexts, mesh_axes={"dp": -1})
    assert counts == {"train_step": 6}
    p_one, _, _, _ = _train(True, mx.cpu())
    for k in p:
        assert np.allclose(p[k], p_one[k], rtol=1e-5, atol=1e-6), k


# ---------------------------------------------------------------------------
# 3. Sharded checkpoints across mesh-shape changes
# ---------------------------------------------------------------------------

@needs_mesh
def test_checkpoint_restores_across_mesh_shapes(tmp_path):
    contexts = [mx.cpu(i) for i in range(8)]
    bs = _batches(6)
    with _pin("1"):
        # oracle: uninterrupted dp-only run over all 6 batches
        oracle = _make(contexts)
        met = mx.metric.Accuracy()
        for b in bs:
            assert oracle._fused_batch_step(b, met)
        p_oracle, _ = oracle.get_params()

        # dp-only for 3 batches -> checkpoint (ONE host file, layout in
        # meta) -> restore onto a dp x mp mesh -> 3 more batches
        a = _make(contexts)
        for b in bs[:3]:
            assert a._fused_batch_step(b, met)
        mgr = mx.CheckpointManager(str(tmp_path / "model"))
        meta = mgr.save(a, 0)
        assert meta["layout"]["mesh_axes"] == {"dp": 8}
        assert meta["layout"]["partition"] is None
        b_mod = _make(contexts, partition_rules=RULES,
                      mesh_axes={"dp": 2, "mp": 4})
        mgr.restore(b_mod)
        for b in bs[3:]:
            assert b_mod._fused_batch_step(b, met), \
                b_mod._fused_fallback_reason
        p_b, _ = b_mod.get_params()
        for k in p_b:
            assert np.allclose(p_b[k].asnumpy(),
                               p_oracle[k].asnumpy(),
                               rtol=1e-5, atol=1e-6), k

        # the dp x mp -> dp-only direction, with the layout recorded
        c = _make(contexts, partition_rules=RULES,
                  mesh_axes={"dp": 2, "mp": 4})
        for b in bs[:3]:
            assert c._fused_batch_step(b, met)
        mgr2 = mx.CheckpointManager(str(tmp_path / "m2"))
        meta2 = mgr2.save(c, 0)
        assert meta2["layout"]["mesh_axes"] == {"dp": 2, "mp": 4}
        assert set(meta2["layout"]["partition"]["sharded_params"]) == {
            "fc1_weight", "fc1_bias", "fc2_weight", "fc2_bias"}
        d = _make(contexts)
        mgr2.restore(d)
        for b in bs[3:]:
            assert d._fused_batch_step(b, met)
        p_d, _ = d.get_params()
        for k in p_d:
            assert np.allclose(p_d[k].asnumpy(),
                               p_oracle[k].asnumpy(),
                               rtol=1e-5, atol=1e-6), k


@needs_mesh
def test_sync_state_recommits_to_rule_placement(tmp_path):
    """The Updater._sync_state regression (dp x mp round trip): loaded
    optimizer states re-commit to the WEIGHT's rule-derived placement,
    not the replicated dp layout the old code assumed."""
    contexts = [mx.cpu(i) for i in range(8)]
    bs = _batches(4)
    with _pin("1"):
        a = _make(contexts, partition_rules=RULES,
                  mesh_axes={"dp": 2, "mp": 4})
        met = mx.metric.Accuracy()
        for b in bs[:2]:
            assert a._fused_batch_step(b, met)
        states = tmp_path / "opt.states"
        a.save_optimizer_states(str(states))

        b_mod = _make(contexts, partition_rules=RULES,
                      mesh_axes={"dp": 2, "mp": 4})
        arg_p, aux_p = a.get_params()
        b_mod.set_params(arg_p, aux_p)
        b_mod.load_optimizer_states(str(states))
        for b in bs[2:]:
            assert b_mod._fused_batch_step(b, met), \
                b_mod._fused_fallback_reason
        # momentum state landed on the weight's mp-sharded placement
        upd = b_mod._kvstore._updater if b_mod._update_on_kvstore \
            else b_mod._updater
        i = b_mod._param_names.index("fc1_weight")
        st = upd.states[i]
        leaf = st[0] if isinstance(st, tuple) else st
        wsh = b_mod._exec.arg_dict["fc1_weight"]._data.sharding
        assert leaf._data.sharding.spec == wsh.spec
        assert "mp" in tuple(leaf._data.sharding.spec)
        # and the round trip is exact: continuing A is bit-identical
        for b in bs[2:]:
            assert a._fused_batch_step(b, met)
        pa, _ = a.get_params()
        pb, _ = b_mod.get_params()
        for k in pa:
            assert np.array_equal(pa[k].asnumpy(), pb[k].asnumpy()), k


# ---------------------------------------------------------------------------
# 4. Serving with mp-sharded params
# ---------------------------------------------------------------------------

@needs_mesh
def test_serving_mp_sharded_bit_equal_to_replicated():
    from mxnet_tpu.serving import InferenceEngine
    net = sym.FullyConnected(sym.Variable("data"), num_hidden=64,
                             name="fc1")
    net = sym.Activation(net, act_type="relu")
    rs = np.random.RandomState(3)
    params = {
        "arg:fc1_weight": nd.array(
            rs.uniform(-1, 1, (64, 16)).astype(np.float32)),
        "arg:fc1_bias": nd.array(
            rs.uniform(-1, 1, (64,)).astype(np.float32)),
    }
    rules = PartitionRules([(r"fc1_weight$", P("mp", None)),
                            (r"fc1_bias$", P("mp"))])
    x = rs.uniform(-1, 1, (5, 16)).astype(np.float32)
    with InferenceEngine(net, params, {"data": (8, 16)},
                         max_batch=8) as repl:
        r_repl = repl.predict(data=x)
        r_repl1 = repl.predict(data=x[:1])
    contexts = [mx.cpu(i) for i in range(8)]
    with InferenceEngine(net, params, {"data": (8, 16)}, max_batch=8,
                         partition_rules=rules,
                         contexts=contexts) as eng:
        # params really live mp-sharded across the serving mesh
        w = eng._param_raw["fc1_weight"]
        assert "mp" in tuple(w.sharding.spec)
        assert len(w.addressable_shards) == 8
        summary = eng.partition_summary()
        assert summary["mesh_axes"] == {"dp": 1, "mp": 8}
        r_mp = eng.predict(data=x)
        # a second request exercises a different bucket
        r_mp1 = eng.predict(data=x[:1])
    # per-bucket comparison: each bucket's program vs the SAME bucket
    # on the replicated engine; the mp-sharded program is still another
    # program (each device's dot is an eighth as wide), so equal as far
    # as helpers.assert_equal_across_shapes holds two programs to
    for a, b in zip(r_repl + r_repl1, r_mp + r_mp1):
        assert_equal_across_shapes(a, b)


@needs_mesh
def test_serving_bucket_divisibility_checked_against_dp():
    from mxnet_tpu.serving import InferenceEngine
    net = sym.FullyConnected(sym.Variable("data"), num_hidden=8,
                             name="fc1")
    rs = np.random.RandomState(0)
    params = {
        "arg:fc1_weight": nd.array(
            rs.uniform(-1, 1, (8, 4)).astype(np.float32)),
        "arg:fc1_bias": nd.array(np.zeros(8, np.float32)),
    }
    rules = PartitionRules([(r".*weight$", P("mp", None))])
    with pytest.raises(MXNetError, match="'dp' mesh axis"):
        InferenceEngine(net, params, {"data": (8, 4)}, max_batch=8,
                        buckets=[1, 8], warmup=False,
                        partition_rules=rules,
                        mesh_axes={"dp": 2, "mp": 4},
                        contexts=[mx.cpu(i) for i in range(8)])


# ---------------------------------------------------------------------------
# 5. The parallel kernels' exported layouts
# ---------------------------------------------------------------------------

def test_kernels_export_partition_rules():
    import importlib
    from mxnet_tpu.parallel import moe, pipeline, ulysses
    # the package re-exports the ring_attention FUNCTION under the
    # submodule's name; import the module explicitly
    ring_attention = importlib.import_module(
        "mxnet_tpu.parallel.ring_attention")
    fake = {
        "router_w": (4, 32), "blk0_expert_w1": (4, 64, 32),
        "stage_stack": (4, 8, 8),
        "q_proj_weight": (64, 32), "out_proj_weight": (32, 64),
        "ln_gamma": (32,),
    }
    for mod, axis in ((moe, "ep"), (pipeline, "pp"),
                      (ring_attention, None), (ulysses, "sp")):
        rules = PartitionRules(mod.PARTITION_RULES)
        specs = rules.apply(fake)
        flat_axes = {a for s in specs.values()
                     for e in tuple(s) if e is not None
                     for a in (e if isinstance(e, tuple) else (e,))}
        if axis is None:
            assert flat_axes == set(), flat_axes
        else:
            assert flat_axes == {axis}, (mod.__name__, flat_axes)
    # the moe rules route router vs expert weights differently
    moe_specs = PartitionRules(moe.PARTITION_RULES).apply(fake)
    assert tuple(moe_specs["router_w"]) == ()
    assert tuple(moe_specs["blk0_expert_w1"]) == ("ep",)


def test_plan_serving_layout_filter_both_directions():
    """The tuner's layout filter ALWAYS applies: mp-sharded corpus rows
    never shape a replicated engine's plan and vice versa — and the
    derived ``sharded_params`` map (absent at plan-load time, present
    on banked rows) does not split otherwise-identical layouts."""
    from mxnet_tpu.tuner import plan_serving

    def rec(layout=None):
        return {"kind": "serving", "max_batch": 16, "layout": layout,
                "rows_hist": {"3": 50, "16": 5},
                "bucket_ms": {"16": {"total_ms": 160.0, "count": 10}},
                "spans": {}}

    banked = {"mesh_axes": {"dp": 1, "mp": 8}, "data_axis": "dp",
              "partition": {"rules": [["w$", ["mp"]]],
                            "unmatched": "replicate",
                            "sharded_params": {"w": ["mp"]}}}
    query = {"mesh_axes": {"dp": 1, "mp": 8}, "data_axis": "dp",
             "partition": {"rules": [["w$", ["mp"]]],
                           "unmatched": "replicate"}}
    # replicated engine ignores mp rows (and still plans from its own)
    assert plan_serving([rec(banked)], layout=None) is None
    assert plan_serving([rec(None)], layout=None) is not None
    # mp engine plans from mp rows despite the sharded_params delta,
    # and ignores replicated rows
    plan = plan_serving([rec(banked), rec(None)], layout=query)
    assert plan is not None
    assert plan["basis"]["records"] == 1
    assert plan["layout"] == query
    # a genuinely different layout (other mesh) never matches
    other = dict(query, mesh_axes={"dp": 1, "mp": 4})
    assert plan_serving([rec(banked)], layout=other) is None


# ---------------------------------------------------------------------------
# 6. Ledger / summary helpers
# ---------------------------------------------------------------------------

@needs_mesh
def test_committed_nbytes_counts_per_shard():
    contexts = [mx.cpu(i) for i in range(8)]
    mesh = mesh_from_contexts(contexts, axes={"dp": 2, "mp": 4})
    spec = rule_spec(mesh, RULES)
    w = jax.device_put(np.zeros((64, 16), np.float32),
                       spec.param_sharding("fc1_weight", (64, 16)))
    # sharded over mp=4: 2048 bytes/shard-group x 8 devices = 2x global
    assert committed_nbytes(w) == 64 * 16 * 4 // 4 * 8
    r = jax.device_put(np.zeros((64,), np.float32), spec.repl_sharding)
    assert committed_nbytes(r) == 64 * 4 * 8


@needs_mesh
def test_partition_summary_shape():
    contexts = [mx.cpu(i) for i in range(8)]
    spec = rule_spec(mesh_from_contexts(contexts,
                                        axes={"dp": 2, "mp": 4}), RULES)
    s = partition_summary(spec, {"fc1_weight": (64, 16), "other": (3,)})
    assert s["mesh_axes"] == {"dp": 2, "mp": 4}
    assert s["data_axis"] == "dp"
    assert s["partition"]["unmatched"] == "replicate"
    assert s["partition"]["sharded_params"] == {
        "fc1_weight": ["mp", None]}
    assert partition_summary(None) is None
