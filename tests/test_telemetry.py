"""Unified runtime telemetry suite (ISSUE 3): counter registry, host-span
tracing, multi-subscriber dispatch registry, fused-fallback logging, the
merged host+device chrome trace, and the tier-1 <2% overhead guard."""
import collections
import json
import logging
import os
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import telemetry


@pytest.fixture(autouse=True)
def _clean_registry():
    """Each test sees a fresh, enabled registry and leaves it that way
    (telemetry is process-global)."""
    telemetry.enable()
    telemetry.reset()
    yield
    telemetry.enable()
    telemetry.reset()


# ---------------------------------------------------------------------------
# Registry basics
# ---------------------------------------------------------------------------

def test_counters_and_reset():
    telemetry.counter_inc("a")
    telemetry.counter_inc("a", 4)
    telemetry.counter_inc("b")
    assert telemetry.counters() == {"a": 5, "b": 1}
    telemetry.reset()
    assert telemetry.counters() == {}


def test_span_records_histogram_and_percentiles():
    for _ in range(20):
        with telemetry.span("phase"):
            pass
    stats = telemetry.span_stats("phase")["phase"]
    assert stats["count"] == 20
    assert stats["p50_ms"] <= stats["p95_ms"] <= stats["p99_ms"] \
        <= stats["max_ms"]
    assert stats["total_ms"] >= 0
    snap = telemetry.snapshot()
    assert "phase" in snap["spans"] and snap["enabled"] is True


def test_disable_stops_recording():
    telemetry.disable()
    with telemetry.span("off"):
        pass
    telemetry.counter_inc("off", 3)
    telemetry.enable()
    assert telemetry.counters() == {}
    assert telemetry.span_stats("off") == {}


def test_span_ring_is_bounded():
    for i in range(telemetry.SPAN_RING_SIZE + 100):
        with telemetry.span("ring"):
            pass
    assert len(telemetry.chrome_events(since_trace_start=False)) \
        <= telemetry.SPAN_RING_SIZE + 16   # + metadata rows


# ---------------------------------------------------------------------------
# Multi-subscriber dispatch registry (+ legacy single-slot shim)
# ---------------------------------------------------------------------------

def test_dispatch_multi_subscriber_and_legacy_shim():
    import mxnet_tpu.executor as _ex
    seen_a, seen_b, legacy = [], [], []
    cb_a = telemetry.on_dispatch(seen_a.append)
    cb_b = telemetry.on_dispatch(seen_b.append)
    old = _ex.dispatch_hook
    _ex.dispatch_hook = legacy.append
    try:
        _ex.record_dispatch("k1")
    finally:
        _ex.dispatch_hook = old
        telemetry.remove_dispatch(cb_a)
        telemetry.remove_dispatch(cb_b)
    # every subscriber AND the legacy slot saw the dispatch — no
    # clobbering — and the counter registry recorded it too
    assert seen_a == ["k1"] and seen_b == ["k1"] and legacy == ["k1"]
    assert telemetry.dispatch_counts() == {"k1": 1}
    # removal is effective and idempotent
    _ex.record_dispatch("k2")
    assert seen_a == ["k1"]
    telemetry.remove_dispatch(cb_a)   # second remove: no error


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


def _fit(mod, it, metric, n_epoch=1, **kwargs):
    mod.fit(it, eval_metric=metric, num_epoch=n_epoch,
            initializer=mx.initializer.Xavier(), optimizer="sgd",
            optimizer_params={"learning_rate": 0.05}, **kwargs)


# ---------------------------------------------------------------------------
# Module integration: snapshot + fallback accounting
# ---------------------------------------------------------------------------

def test_module_fit_snapshot_fused():
    mod = mx.mod.Module(_mlp(), context=mx.cpu())
    metric = mx.metric.Accuracy()
    _fit(mod, _iter(6), metric)
    telemetry.reset()
    _fit(mod, _iter(6), metric)
    snap = mod.telemetry_snapshot()
    assert snap["fused_fallback_code"] is None
    c = snap["counters"]
    # ONE whole-step program per batch, no phase-split dispatches
    assert c.get("dispatch.train_step") == 6
    assert "dispatch.fwd_bwd" not in c
    # the second fit reuses the cached plan: no new train_step compile
    assert c.get("jit.compile.train_step", 0) == 0
    # step-span percentiles present and ordered
    st = snap["spans"]["step"]
    assert st["count"] == 6
    assert st["p50_ms"] <= st["p95_ms"] <= st["p99_ms"]
    for name in ("fit_batch", "feed", "io_next"):
        assert name in snap["spans"], name


def test_module_fit_fallback_counted_and_logged_once(caplog):
    os.environ["MXNET_MODULE_FUSED_STEP"] = "0"
    try:
        mod = mx.mod.Module(_mlp(), context=mx.cpu())
        metric = mx.metric.Accuracy()
        with caplog.at_level(logging.WARNING, logger="mxnet_tpu.module"):
            _fit(mod, _iter(5), metric)
    finally:
        os.environ.pop("MXNET_MODULE_FUSED_STEP", None)
    snap = mod.telemetry_snapshot()
    # every phase-split step counted under the STABLE code...
    assert snap["counters"].get("fused_fallback.env_pin") == 5
    assert snap["fused_fallback_code"] == "env_pin"
    # ...but logged ONCE per module, with the code in the message
    msgs = [r.message for r in caplog.records
            if "fused-step fallback" in r.message]
    assert len(msgs) == 1 and "code=env_pin" in msgs[0]
    # phase-split dispatch mix: fwd_bwd + opt_update + metric per batch
    c = snap["counters"]
    assert c.get("dispatch.fwd_bwd") == 5
    assert c.get("dispatch.opt_update") == 5


def test_host_sync_and_transfer_counters():
    a = mx.nd.ones((8, 8))
    telemetry.reset()
    a.asnumpy()
    a.wait_to_read()
    c = telemetry.counters()
    assert c.get("host_sync.blocking") == 2
    assert c.get("host_sync.asnumpy") == 1
    assert c.get("host_sync.wait_to_read") == 1
    assert c.get("transfer.d2h_bytes") == 8 * 8 * 4


# ---------------------------------------------------------------------------
# Merged host+device chrome trace (the acceptance artifact)
# ---------------------------------------------------------------------------

def test_fit_profiler_merged_chrome_trace(tmp_path):
    """A Module.fit run under profiler.set_state('run') must yield ONE
    chrome-trace JSON containing BOTH device ops and the host spans
    (feed/shard_put/step/metric_fetch) — the unified perfetto view. The
    spans are the profiler's own slices (annotations on its clock, the
    step ids as args); nothing of a fit loop is merged from the ring."""
    fname = str(tmp_path / "merged_profile.json")
    mx.profiler.set_config(filename=fname)
    # two contexts: the dp mesh exercises the shard_put feed path
    mod = mx.mod.Module(_mlp(), context=[mx.cpu(0), mx.cpu(1)])
    metric = mx.metric.Accuracy()
    _fit(mod, _iter(4), metric)          # bind+compile outside the trace
    mx.profiler.set_state("run")
    _fit(mod, _iter(4), metric)
    metric.get()                         # metric host sync inside window
    mx.profiler.set_state("stop")
    mx.profiler.dump()
    with open(fname) as f:
        trace = json.load(f)
    events = trace["traceEvents"]
    slices = [e for e in events if e.get("ph") == "X"]
    names = collections.Counter(e["name"] for e in slices)
    assert {"feed", "shard_put", "step", "metric_fetch"} <= set(names), names
    assert names["fit_batch"] == names["feed"] == names["step"] == 4
    assert sorted(int(e["args"]["nbatch"]) for e in slices
                  if e["name"] == "feed") == [0, 1, 2, 3]
    assert len(slices) > sum(names[n] for n in telemetry.FIT_PHASE_SPANS), \
        "device ops missing from the merged trace"
    # one path per span: none came a second time from the ring, and no
    # empty "mxnet_tpu host" track is added for them
    assert not [e for e in events if e.get("cat") == "host"]
    assert not any(e.get("ph") == "M" and e.get("name") == "process_name"
                   and e["args"]["name"] == "mxnet_tpu host"
                   for e in events)
    assert trace["otherData"]["mxnet_tpu_programs"]


# ---------------------------------------------------------------------------
# TelemetryLogger callback
# ---------------------------------------------------------------------------

def test_telemetry_logger_callback(caplog):
    mod = mx.mod.Module(_mlp(), context=mx.cpu())
    metric = mx.metric.Accuracy()
    with caplog.at_level(logging.INFO, logger="mxnet_tpu.telemetry"):
        _fit(mod, _iter(6), metric,
             batch_end_callback=mx.callback.TelemetryLogger(frequent=2))
    lines = [r.message for r in caplog.records
             if "dispatches/batch" in r.message]
    assert lines, "TelemetryLogger logged nothing"
    assert "jit compile/hit" in lines[-1]
    # steady-state window: one fused dispatch per batch
    assert "dispatches/batch=1.00" in lines[-1]


# ---------------------------------------------------------------------------
# Tier-1 overhead guard (<2% on the CPU smoke workload)
# ---------------------------------------------------------------------------

def test_telemetry_overhead_guard(tmp_path):
    """Telemetry-enabled Module.fit must do under 100 us of telemetry
    work a batch: a thousandth of the 107 ms step the chip's benchmark
    cell runs (PERF.md has the chip's on/off readings). A wall-clock A/B
    cannot resolve that here: share-throttled CI boxes burst-stall at
    sub-epoch granularity (adjacent-leg ratios swing 0.4x-2.2x), and a
    ratio against this CPU smoke workload's own 1.6 ms batch says
    nothing of a chip. The guard instead bounds the measured telemetry
    WORK: count the actual per-batch registry operations the fit loop
    performs (the registry reports its own op counts exactly — spans,
    counters, the ISSUE-4 paths (buffer-ledger tracks and program-card
    dispatch bumps) AND the ISSUE-10 flight-recorder paths: causal-id
    spans, discrete events, and the metrics sampler's ticks, which run
    DURING the counted epoch), microbenchmark the per-op costs (min over
    repeated tight loops — robust to throttle, which can only inflate
    them) and assert ops x cost under the budget. A span's cost is
    measured as the fit loop pays it since ISSUE 25: inside a causal
    scope AND entering its profiler annotation with no session running
    (a flag test in C++ plus the keyword packing), which must itself
    stay under 10 us. A lock storm or a heavy span/ledger/card/sampler
    path fails this at once; box noise cannot."""
    from mxnet_tpu import flight
    batch, nbatch = 512, 12
    rs = np.random.RandomState(0)
    X = rs.uniform(-1, 1, (batch * nbatch, 64)).astype(np.float32)
    Y = rs.randint(0, 8, batch * nbatch).astype(np.float32)
    mod = mx.mod.Module(_mlp(hidden=256, classes=8), context=mx.cpu())
    metric = mx.metric.Accuracy()

    def epoch():
        it = mx.io.NDArrayIter(X, Y, batch_size=batch)
        t0 = time.perf_counter()
        _fit(mod, it, metric)
        metric.get()
        float(np.asarray(
            mod._exec.arg_dict[mod._param_names[0]]._data).sum())
        return time.perf_counter() - t0

    epoch()  # warm: bind + compile outside every timed window
    # batch-time floor over a few epochs (min: throttle only inflates)
    batch_s = min(epoch() for _ in range(5)) / nbatch

    # exact per-batch telemetry op counts from the steady-state epoch —
    # with the flight-recorder sampler RUNNING, as the acceptance gate
    # demands (its ticks are counted and costed like every other op)
    telemetry.reset()
    flight.series_clear()
    sampler_interval_s = 0.02
    flight.sampler_start(sampler_interval_s * 1e3)
    try:
        epoch()
    finally:
        flight.sampler_stop()
    ticks = len(flight.series()) / nbatch
    flight.series_clear()
    spans = sum(telemetry.span_count(n)
                for n in telemetry.span_stats()) / nbatch
    counts = telemetry.counters()
    counter_ops = sum(v for k, v in counts.items()
                      if k.endswith("_count") or k.startswith(
                          ("dispatch.", "host_sync.", "jit."))) / nbatch
    event_ops = len(telemetry.events()) / nbatch
    # ISSUE-4 instrumentation: buffer-ledger tracks (NDArray wraps,
    # shard_put) and program-card dispatch bumps the epoch performed
    ledger_ops = sum(st.get("tracked_total", 0)
                     for st in telemetry.ledger().values()) / nbatch
    card_ops = sum(c.get("dispatches", 0)
                   for c in telemetry.programs().values()) / nbatch
    # ISSUE-18 instrumentation: gate crossings the epoch performed
    # (zero in this single-process workload — the dist fit loop pays
    # one per batch, priced below at the measured per-crossing cost)
    gate_ops = sum(v for k, v in counts.items()
                   if k.startswith("heartbeat.gate_crossings.")) / nbatch

    def op_cost(fn, iters=20000, reps=5):
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter_ns()
            for _ in range(iters):
                fn()
            best = min(best, (time.perf_counter_ns() - t0) / iters)
        return best / 1e9

    def one_span():
        # measured INSIDE a causal scope: every fit-loop span now pays
        # the ambient-ids capture, so the probe must too
        with telemetry.span("_guard_probe"):
            pass

    class _Obj:
        pass

    def one_track():
        # full lifecycle: track + immediate finalize on refcount drop
        telemetry.ledger_track(_Obj(), "cpu(0)", 128,
                               shape=(32,), dtype="float32")

    _card = {"id": "_guard_card"}
    assert telemetry._annotation is not None    # the idle annotation rides
    with telemetry.causal(epoch=0, nbatch=0):
        span_s = op_cost(one_span)
    assert span_s < 10e-6, \
        "a span with its idle annotation costs %.1f us" % (span_s * 1e6)
    counter_s = op_cost(lambda: telemetry.counter_inc("_guard_probe"))
    event_s = op_cost(lambda: telemetry.record_event("_guard_probe"))
    track_s = op_cost(one_track, iters=5000)
    card_s = op_cost(lambda: telemetry.program_dispatch(_card))
    tick_s = op_cost(lambda: flight._build_sample({},
                                                  sampler_interval_s),
                     iters=500)
    # per-crossing gate attribution (ISSUE 18): _record_crossing on a
    # REAL two-member gate directory — the arrival-file scan, the
    # span/counter records and the streak machine, exactly what every
    # dist-step crossing pays after its barrier completes
    from mxnet_tpu import heartbeat
    groot = str(tmp_path)
    gate = heartbeat.CollectiveGate(0, (0, 1), root=groot, poll=0.05)
    gate._publish(1, self_ms=5.0)
    with open(gate._member_path(1), "w") as f:
        f.write("1 %.6f 5.0" % time.time())
    crossing_s = op_cost(
        lambda: gate._record_crossing(1, time.perf_counter_ns()),
        iters=2000)
    overhead_s = spans * span_s + counter_ops * counter_s \
        + event_ops * event_s + ledger_ops * track_s \
        + card_ops * card_s + ticks * tick_s + gate_ops * crossing_s
    telemetry.reset()
    # the dist fit loop pays ONE crossing per batch, and every crossing
    # already waits at least one gate-poll interval in steady state —
    # attribution must stay under 2% of that per-crossing floor, so it
    # can never add 2% to a dist step's wall time
    assert crossing_s < 0.02 * gate.poll, \
        "gate attribution %.1fus/crossing exceeds 2%% of the %.0fms " \
        "gate poll quantum" % (crossing_s * 1e6, gate.poll * 1e3)
    assert spans >= 6       # fit_batch, io_next, feed, step_prep, step,
    #                         step_install: every one is priced
    assert overhead_s < 100e-6, \
        "telemetry work %.1fus/batch (%.1f spans x %.2fus + %.1f counter " \
        "ops x %.2fus + %.1f events x %.2fus + %.1f ledger tracks x " \
        "%.2fus + %.1f card bumps x %.2fus + %.2f sampler ticks x " \
        "%.1fus) exceeds the 100us budget (this box's own batch floor: " \
        "%.0fus)" % (overhead_s * 1e6, spans, span_s * 1e6, counter_ops,
                     counter_s * 1e6, event_ops, event_s * 1e6,
                     ledger_ops, track_s * 1e6, card_ops, card_s * 1e6,
                     ticks, tick_s * 1e6, batch_s * 1e6)
