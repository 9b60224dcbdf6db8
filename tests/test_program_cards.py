"""Program cards (ISSUE 4): per-program XLA cost/memory introspection
through the executor's instrumented compile wrapper, recompile-cause
diagnosis, the live device-buffer ledger, and enriched OOM errors."""
import gc
import json
import logging

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import executor as _ex
from mxnet_tpu import telemetry
from mxnet_tpu.io import DataBatch, DataDesc


@pytest.fixture(autouse=True)
def _clean_registry():
    """Fresh, enabled registry per test; the once-per-cause recompile
    warning set is cleared so each test sees its own first warning."""
    telemetry.enable()
    telemetry.reset()
    _ex._RECOMPILE_WARNED.clear()
    yield
    telemetry.enable()
    telemetry.reset()
    _ex._RECOMPILE_WARNED.clear()


def _mlp(hidden=32, classes=4):
    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=hidden, name="fc1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=classes, name="fc2")
    return mx.sym.SoftmaxOutput(net, name="softmax")


def _iter(n_batches, batch=32, d=16, classes=4):
    rs = np.random.RandomState(0)
    X = rs.uniform(-1, 1, (batch * n_batches, d)).astype(np.float32)
    Y = rs.randint(0, classes, batch * n_batches).astype(np.float32)
    return mx.io.NDArrayIter(X, Y, batch_size=batch)


def _fit(mod, it, n_epoch=1, **kwargs):
    mod.fit(it, eval_metric=mx.metric.Accuracy(), num_epoch=n_epoch,
            initializer=mx.initializer.Xavier(), optimizer="sgd",
            optimizer_params={"learning_rate": 0.05}, **kwargs)


def _batch(batch=32, d=16, classes=4, seed=0):
    rs = np.random.RandomState(seed)
    return DataBatch(
        [mx.nd.array(rs.uniform(-1, 1, (batch, d)).astype(np.float32))],
        [mx.nd.array(rs.randint(0, classes, batch).astype(np.float32))],
        pad=0)


def _cards(kind=None):
    cards = telemetry.programs().values()
    return [c for c in cards if kind is None or c["kind"] == kind]


# ---------------------------------------------------------------------------
# Card capture: forward / fwd_bwd / train_step with real cost figures
# ---------------------------------------------------------------------------

def test_cards_for_all_entry_points():
    ex = _mlp().simple_bind(ctx=mx.cpu(), grad_req="write", type_dict={},
                            data=(32, 16), softmax_label=(32,))
    ex.forward(is_train=False)
    ex.forward_backward()
    mod = mx.mod.Module(_mlp(), context=mx.cpu())
    _fit(mod, _iter(4))

    for kind in ("forward", "fwd_bwd", "train_step"):
        cards = _cards(kind)
        assert cards, "no %s card captured" % kind
        card = cards[0]
        # the CPU backend's cost model yields real nonzero figures
        assert card["flops"] and card["flops"] > 0, card
        assert card["bytes_accessed"] and card["bytes_accessed"] > 0
        assert card["peak_bytes"] and card["peak_bytes"] > 0
        assert card["argument_bytes"] > 0 and card["output_bytes"] > 0
        assert card["compile_ms"] > 0 and card["trace_ms"] >= 0
        assert card["dispatches"] >= 1
        # the abstract input signature names the fed arguments
        paths = [e[0] for e in card["signature"]]
        assert any("data" in p for p in paths), paths

    # the whole-step program donates params/states/acc/aux
    ts = _cards("train_step")[0]
    assert ts["donated"] == [0, 1, 2, 3]
    assert ts["dispatches"] == 4


def test_train_step_card_on_dp_mesh():
    """The 8-device CPU mesh smoke lane's acceptance view: the SPMD
    train-step program cards with nonzero FLOPs and memory figures."""
    import jax
    n = min(8, jax.device_count())
    assert n >= 2, "needs the virtual multi-device CPU mesh"
    mod = mx.mod.Module(_mlp(), context=[mx.cpu(i) for i in range(n)])
    _fit(mod, _iter(4))
    assert mod._fused_fallback_reason is None
    cards = _cards("train_step")
    assert cards, telemetry.programs()
    card = cards[0]
    assert card["spmd_devices"] == n
    assert card["flops"] > 0 and card["bytes_accessed"] > 0
    assert card["peak_bytes"] > 0
    assert card["dispatches"] == 4
    # snapshot embeds the same cards (Module.telemetry_snapshot path)
    snap = mod.telemetry_snapshot()
    assert any(c["kind"] == "train_step" and c["spmd_devices"] == n
               for c in snap["programs"].values())


def test_jit_cache_reuse_keeps_one_card():
    """A second fit over the same shapes must reuse the compiled
    program: same card, dispatch count grows, no new compile."""
    mod = mx.mod.Module(_mlp(), context=mx.cpu())
    _fit(mod, _iter(3))
    n_cards = len(_cards("train_step"))
    _fit(mod, _iter(3))
    assert len(_cards("train_step")) == n_cards
    assert _cards("train_step")[0]["dispatches"] == 6
    assert "recompile.train_step" not in telemetry.counters()


# ---------------------------------------------------------------------------
# Recompile-cause diagnosis
# ---------------------------------------------------------------------------

def test_recompile_cause_warning_names_changed_shape(caplog):
    mod = mx.mod.Module(_mlp(), context=mx.cpu())
    mod.bind(data_shapes=[DataDesc("data", (32, 16))],
             label_shapes=[DataDesc("softmax_label", (32,))],
             for_training=True)
    mod.init_params(initializer=mx.initializer.Xavier())
    with caplog.at_level(logging.WARNING, logger="mxnet_tpu.executor"):
        mod.forward(_batch(32), is_train=False)      # first compile
        mod.forward(_batch(16), is_train=False)      # batch-shape flip
        mod.forward(_batch(16), is_train=False)      # cached: no warning
        mod.forward(_batch(32), is_train=False)      # cached: no warning
        mod.forward(_batch(8), is_train=False)       # same cause: warned once
    msgs = [r.message for r in caplog.records if "recompile" in r.message]
    assert len(msgs) == 1, msgs
    # the structured warning names the exact arg and the dimension flip
    assert "data" in msgs[0] and "shape" in msgs[0]
    assert "32" in msgs[0] and "16" in msgs[0]
    # every recompile counted, even the suppressed-warning ones
    assert telemetry.counters().get("recompile.forward") == 2
    # the new card records its causes for snapshot readers
    carded = [c for c in _cards("forward") if c.get("recompile_causes")]
    assert carded and any("shape" in cause
                          for cause in carded[0]["recompile_causes"])


def test_recompile_dtype_flip_named(caplog):
    """A dtype change (not shape) must be named as such."""
    ex = _mlp().simple_bind(ctx=mx.cpu(), grad_req="write", type_dict={},
                            data=(8, 16), softmax_label=(8,))
    ex.forward(is_train=False)
    import jax.numpy as jnp
    ex.arg_dict["data"]._set_data(
        jnp.zeros((8, 16), jnp.float16))             # dtype flip
    with caplog.at_level(logging.WARNING, logger="mxnet_tpu.executor"):
        ex.forward(is_train=False)
    msgs = [r.message for r in caplog.records if "recompile" in r.message]
    assert len(msgs) == 1 and "dtype" in msgs[0], msgs
    assert "float16" in msgs[0]


# ---------------------------------------------------------------------------
# Live device-buffer ledger
# ---------------------------------------------------------------------------

def test_ledger_ndarray_lifecycle():
    key = str(mx.cpu())

    def stats():
        return telemetry.ledger().get(key) or {
            "alive_bytes": 0, "alive_count": 0, "peak_bytes": 0,
            "tracked_total": 0, "tracked_bytes_total": 0}

    # drain cyclic garbage EARLIER tests left alive on this shared
    # context first: the collect below would otherwise reclaim their
    # buffers mid-test and shift the deltas (order-dependent with the
    # native build enabled, which runs more predecessors)
    gc.collect()
    base = stats()
    a = mx.nd.zeros((64, 64))                        # 16 KiB fp32
    after_a = stats()
    assert after_a["alive_bytes"] - base["alive_bytes"] == 64 * 64 * 4
    assert after_a["alive_count"] - base["alive_count"] == 1
    assert after_a["peak_bytes"] >= after_a["alive_bytes"]
    b = mx.nd.ones((32,))
    peak = stats()["peak_bytes"]
    del a
    gc.collect()
    after_del = stats()
    assert after_del["alive_bytes"] - base["alive_bytes"] == 32 * 4
    assert after_del["peak_bytes"] == peak           # high-water stays
    assert after_del["tracked_total"] - base["tracked_total"] == 2
    # the live buffer map backs ledger_top
    top = telemetry.ledger_top(64)
    assert any(t["shape"] == [32] and t["ctx"] == key for t in top)
    del b
    gc.collect()
    assert stats()["alive_bytes"] == base["alive_bytes"]


def test_ledger_shard_put():
    import jax
    from mxnet_tpu.parallel import mesh as _pmesh, spmd as _spmd
    n = min(8, jax.device_count())
    assert n >= 2
    spec = _spmd.dp_spec(_pmesh.mesh_from_contexts(
        [mx.cpu(i) for i in range(n)]))
    key = "mesh(%ddev)" % n
    base = (telemetry.ledger().get(key) or {"alive_bytes": 0})["alive_bytes"]
    out = _spmd.shard_put(np.ones((n * 2, 4), np.float32),
                          spec.data_sharding)
    st = telemetry.ledger()[key]
    assert st["alive_bytes"] - base == n * 2 * 4 * 4
    assert any(t["kind"] == "shard_put" for t in telemetry.ledger_top(64))
    del out
    gc.collect()
    assert telemetry.ledger()[key]["alive_bytes"] == base


def test_ledger_disabled_is_silent_and_consistent():
    """Arrays created while disabled are not charged, and arrays
    created while enabled release correctly even if freed while
    disabled — toggling never corrupts the counters."""
    key = str(mx.cpu())
    a = mx.nd.zeros((16, 16))
    base = telemetry.ledger()[key]["alive_bytes"]
    telemetry.disable()
    b = mx.nd.zeros((128, 128))                      # untracked
    assert telemetry.ledger()[key]["alive_bytes"] == base
    del a                                            # tracked: releases
    gc.collect()
    telemetry.enable()
    assert telemetry.ledger()[key]["alive_bytes"] == base - 16 * 16 * 4
    del b


def test_ledger_release_is_lock_free():
    """The weakref.finalize callback must never take the registry
    lock: cyclic GC (autograd tapes make NDArray cycles) can run it
    synchronously on a thread that already HOLDS the lock — a
    lock-taking finalizer deadlocks the process. The release enqueues
    lock-free and the next ledger operation drains it."""
    key = str(mx.cpu())
    a = mx.nd.zeros((16,))
    base = telemetry.ledger()[key]["alive_bytes"]
    with telemetry._lock:
        del a
        gc.collect()          # finalizer fires while WE hold the lock
    assert telemetry.ledger()[key]["alive_bytes"] == base - 16 * 4


def test_storage_ledger_report():
    from mxnet_tpu.storage import Storage
    a = mx.nd.zeros((8, 8))
    rep = Storage.ledger_report()
    assert str(mx.cpu()) in rep["contexts"]
    assert isinstance(rep["top_buffers"], list)
    json.dumps(rep)                                  # artifact-safe
    del a


# ---------------------------------------------------------------------------
# Enriched OOM errors
# ---------------------------------------------------------------------------

def test_oom_enriched_with_ledger_and_card(monkeypatch):
    ex = _mlp().simple_bind(ctx=mx.cpu(), grad_req="write", type_dict={},
                            data=(8, 16), softmax_label=(8,))
    ex.forward(is_train=False)                       # compile for real
    hog = mx.nd.zeros((512, 512))                    # a nameable suspect

    fake = RuntimeError("RESOURCE_EXHAUSTED: Out of memory while trying "
                        "to allocate 9123456 bytes.")

    def boom(self, fn, args):
        raise fake

    monkeypatch.setattr(_ex._InstrumentedProgram, "_invoke", boom)
    with pytest.raises(_ex.DeviceMemoryError) as ei:
        ex.forward(is_train=False)
    msg = str(ei.value)
    assert "RESOURCE_EXHAUSTED" in msg               # original text kept
    assert "program memory card" in msg and "peak_bytes" in msg
    assert "live device-buffer ledger" in msg
    assert "top live buffers" in msg and "(512, 512)" in msg
    assert ei.value.__cause__ is fake
    del hog


def test_non_oom_errors_pass_through(monkeypatch):
    ex = _mlp().simple_bind(ctx=mx.cpu(), grad_req="write", type_dict={},
                            data=(8, 16), softmax_label=(8,))
    ex.forward(is_train=False)

    def boom(self, fn, args):
        raise RuntimeError("some unrelated backend failure")

    monkeypatch.setattr(_ex._InstrumentedProgram, "_invoke", boom)
    with pytest.raises(RuntimeError, match="unrelated"):
        ex.forward(is_train=False)


# ---------------------------------------------------------------------------
# Graceful degradation
# ---------------------------------------------------------------------------

def test_cards_degrade_when_analysis_unavailable(monkeypatch):
    """cost_analysis/memory_analysis raising (older jaxlib, platform
    quirks) must yield a card with None figures — and dispatch must
    still work."""
    def no_analysis(compiled):
        raise NotImplementedError("not on this backend")

    monkeypatch.setattr(_ex, "_compiled_cost", no_analysis)
    monkeypatch.setattr(_ex, "_compiled_memory", no_analysis)
    ex = _mlp().simple_bind(ctx=mx.cpu(), grad_req="write", type_dict={},
                            data=(8, 16), softmax_label=(8,))
    outs = ex.forward(is_train=False)
    assert outs and outs[0].shape == (8, 4)
    card = _cards("forward")[0]
    assert card["flops"] is None and card["bytes_accessed"] is None
    assert card["peak_bytes"] is None and card["argument_bytes"] is None
    assert card["dispatches"] == 1
    json.dumps(telemetry.snapshot())


def test_dispatch_survives_aot_compile_failure():
    """lower()/compile() blowing up falls back to the plain jitted
    callable; the card records the fallback, fields stay None."""
    prog = _ex._InstrumentedProgram("forward", lambda x: x * 2.0)

    class _BrokenLower:
        def __init__(self, real):
            self._real = real

        def lower(self, *args):
            raise RuntimeError("AOT not supported here")

        def __call__(self, *args):
            return self._real(*args)

    prog._jitted = _BrokenLower(prog._jitted)
    out = prog(np.ones((3,), np.float32))
    assert float(np.asarray(out).sum()) == 6.0
    card = list(telemetry.programs().values())[0]
    assert "AOT not supported" in card["aot_fallback"]
    assert card["flops"] is None and card["peak_bytes"] is None
    assert card["dispatches"] == 1


# ---------------------------------------------------------------------------
# Online MFU estimate + snapshot serializability
# ---------------------------------------------------------------------------

def test_online_mfu_estimate():
    mod = mx.mod.Module(_mlp(), context=mx.cpu())
    _fit(mod, _iter(5))
    snap = telemetry.snapshot()
    online = snap["online"]
    assert online["flops_dispatched"] > 0
    assert online["step_time_s"] > 0
    assert online["dispatch_wall_s"] > 0
    assert online["model_flops_per_s"] > 0
    assert online["mfu"] is None                     # no ceiling known
    telemetry.set_peak_flops(1e12)
    try:
        online = telemetry.snapshot()["online"]
        assert online["peak_flops"] == 1e12
        # the rate is over the dispatch stream's wall time, never over
        # the step spans (tests/test_one_clock.py holds the arithmetic)
        assert online["mfu"] == pytest.approx(
            online["model_flops_per_s"] / 1e12, rel=1e-3)
    finally:
        telemetry.set_peak_flops(None)


def test_snapshot_json_serializable_end_to_end():
    import jax
    n = min(8, jax.device_count())
    mod = mx.mod.Module(_mlp(), context=[mx.cpu(i) for i in range(n)])
    _fit(mod, _iter(3))
    blob = json.dumps(mod.telemetry_snapshot())
    parsed = json.loads(blob)
    assert parsed["programs"] and parsed["online"]["flops_dispatched"] > 0


# ---------------------------------------------------------------------------
# TelemetryLogger programs mode
# ---------------------------------------------------------------------------

def test_telemetry_logger_programs_mode(caplog):
    mod = mx.mod.Module(_mlp(), context=mx.cpu())
    with caplog.at_level(logging.INFO, logger="mxnet_tpu.telemetry"):
        _fit(mod, _iter(4), batch_end_callback=mx.callback.TelemetryLogger(
            frequent=2, programs=True))
    lines = [r.message for r in caplog.records if "program card" in r.message]
    assert lines, "programs=True logged no cards"
    assert any("train_step" in ln and "compile=" in ln and "flops=" in ln
               for ln in lines)
    # each card logged once
    assert len(lines) == len(set(lines))


# ---------------------------------------------------------------------------
# Lint mirror: no raw jax.jit outside the instrumented wrapper
# ---------------------------------------------------------------------------

def test_no_raw_jit_outside_instrumented_wrapper():
    """Tier-1 mirror of the run_checks.sh lint stage, now driving the
    REAL analyzer (mxnet_tpu.analysis jit-site rule) instead of grep:
    every program must compile through _InstrumentedProgram (program
    card, recompile diagnosis, OOM enrichment — and on the serving
    path, the one-compile-per-bucket accounting). Unlike the old grep,
    this resolves import aliases (`from jax import jit as J`) and
    decorator form, package-wide, against the committed grandfather
    baseline."""
    import os
    from mxnet_tpu.analysis import run
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    report = run([os.path.join(root, "mxnet_tpu")],
                 rules=["jit-site"],
                 baseline=os.path.join(root, "tools",
                                       "mxlint_baseline.json"),
                 root=root)
    assert report.clean, [f.render() for f in report.findings]
