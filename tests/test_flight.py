"""Flight recorder suite (ISSUE 10): causal ids on spans + chrome flow
events, the discrete-event ring, the metrics sampler / time-series ring
/ JSONL export, the OpenMetrics endpoint, crash postmortems (explicit
triggers, excepthook, throttle) and the flight_view CLI."""
import json
import logging
import os
import subprocess
import sys
import threading
import time
import urllib.request

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import flight, telemetry

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "tools"))
import flight_view  # noqa: E402  (stdlib-only CLI module)


@pytest.fixture(autouse=True)
def _clean_registry():
    """Fresh telemetry + inert flight recorder around every test (both
    are process-global)."""
    telemetry.enable()
    telemetry.reset()
    flight.sampler_stop()
    flight.series_clear()
    flight.configure(None)
    yield
    flight.sampler_stop()
    flight.metrics_http_stop()
    flight.series_clear()
    flight.configure(None)
    telemetry.enable()
    telemetry.reset()


# ---------------------------------------------------------------------------
# Causal ids
# ---------------------------------------------------------------------------

def test_causal_scope_stamps_spans_and_nests():
    with telemetry.causal(epoch=1, nbatch=7):
        with telemetry.span("feed"):
            pass
        assert telemetry.current_causal() == {"epoch": 1, "nbatch": 7}
        with telemetry.causal(req_id=3):
            with telemetry.span("inner"):
                pass
    assert telemetry.current_causal() is None
    with telemetry.span("outside"):
        pass
    by_name = {s["name"]: s for s in telemetry.recent_spans()}
    assert by_name["feed"]["ctx"] == {"epoch": 1, "nbatch": 7}
    assert by_name["inner"]["ctx"] == {"req_id": 3}
    assert by_name["outside"]["ctx"] is None


def test_span_explicit_ctx_survives_cross_thread_exit():
    # the serving pattern: entered on the submitting thread, exited on
    # a resolver thread — the explicit ctx must ride, not the exiting
    # thread's ambient scope
    sp = telemetry.span("serve_wait", ctx={"req_id": 42}).__enter__()

    def _closer():
        with telemetry.causal(epoch=9, nbatch=9):
            sp.__exit__(None, None, None)

    t = threading.Thread(target=_closer)
    t.start()
    t.join()
    rec = [s for s in telemetry.recent_spans()
           if s["name"] == "serve_wait"]
    assert rec and rec[-1]["ctx"] == {"req_id": 42}


def test_chrome_flow_events_link_shared_ids():
    with telemetry.causal(epoch=0, nbatch=2):
        with telemetry.span("feed"):
            pass
        with telemetry.span("step"):
            pass
    with telemetry.span("serve_batch", ctx={"req_ids": [5, 6]}):
        pass
    with telemetry.span("serve_request", ctx={"req_id": 5}):
        pass
    evs = telemetry.chrome_events(since_trace_start=False)
    # a fit step's spans draw no flow: they are one thread's annotations,
    # nested in their step annotation, with the ids on each
    assert not [e for e in evs if e.get("cat") == "flow"
                and str(e["id"]).startswith("step:")]
    req_flow = [e for e in evs if e.get("cat") == "flow"
                and e["id"] == "req:5"]
    assert [e["ph"] for e in req_flow] == ["s", "f"]
    assert req_flow[-1]["bp"] == "e"
    # a lone id draws no arrow (req 6 appears in ONE span only)
    assert not [e for e in evs if e.get("cat") == "flow"
                and e["id"] == "req:6"]
    # slices carry the causal ids as args for the perfetto tooltip
    feed = [e for e in evs if e.get("ph") == "X" and e["name"] == "feed"]
    assert feed[0]["args"] == {"epoch": 0, "nbatch": 2}


def test_request_flow_chains_in_pipeline_order():
    # the REAL serving shape: serve_request is entered at submit (same
    # instant as serve_wait) and closes last — by start time it would
    # sort second and the chain would terminate at serve_d2h. The flow
    # must chain wait -> batch -> d2h -> request, with the terminal 'f'
    # bound near the serve_request span's END (the resolution instant).
    req_sp = telemetry.span("serve_request",
                            ctx={"req_id": 9}).__enter__()
    with telemetry.span("serve_wait", ctx={"req_id": 9}):
        time.sleep(0.001)
    with telemetry.span("serve_batch", ctx={"req_ids": [9]}):
        time.sleep(0.001)
    with telemetry.span("serve_d2h", ctx={"req_ids": [9]}):
        time.sleep(0.001)
    time.sleep(0.001)
    req_sp.__exit__(None, None, None)
    evs = telemetry.chrome_events(since_trace_start=False)
    flow = [e for e in evs if e.get("cat") == "flow"
            and e["id"] == "req:9"]
    assert [e["ph"] for e in flow] == ["s", "t", "t", "f"]
    slices = {e["name"]: e for e in evs if e.get("ph") == "X"}
    # the chain's nodes bind in pipeline order: wait, batch, d2h
    # starts, then the request terminus
    assert flow[0]["ts"] == slices["serve_wait"]["ts"]
    assert flow[1]["ts"] == slices["serve_batch"]["ts"]
    assert flow[2]["ts"] == slices["serve_d2h"]["ts"]
    req = slices["serve_request"]
    assert flow[3]["bp"] == "e"
    # terminal node sits inside the serve_request slice, AFTER the d2h
    # slice began — the resolution instant, not the submit instant
    assert req["ts"] <= flow[3]["ts"] <= req["ts"] + req["dur"]
    assert flow[3]["ts"] > slices["serve_d2h"]["ts"]


# ---------------------------------------------------------------------------
# Event ring
# ---------------------------------------------------------------------------

def test_event_ring_records_bounded_and_resets():
    telemetry.record_event("serving.shed", req_id=1, cause="admission")
    evs = telemetry.events()
    assert evs[-1]["kind"] == "serving.shed"
    assert evs[-1]["data"] == {"req_id": 1, "cause": "admission"}
    for i in range(telemetry.EVENT_RING_SIZE + 10):
        telemetry.record_event("tick", i=i)
    assert len(telemetry.events()) == telemetry.EVENT_RING_SIZE
    assert telemetry.events(n=3)[-1]["data"] == {
        "i": telemetry.EVENT_RING_SIZE + 9}
    telemetry.reset()
    assert telemetry.events() == []
    telemetry.disable()
    telemetry.record_event("off")
    telemetry.enable()
    assert telemetry.events() == []


# ---------------------------------------------------------------------------
# Metrics sampler + series ring
# ---------------------------------------------------------------------------

def test_sampler_banks_counter_deltas_and_gauges():
    flight.sampler_start(10)
    assert flight.sampler_running()
    assert flight.sampler_interval_ms() == pytest.approx(10.0)
    time.sleep(0.05)
    telemetry.counter_inc("serving.requests", 4)
    telemetry.counter_inc("serving.resolved", 1)
    time.sleep(0.08)
    flight.sampler_stop()
    assert not flight.sampler_running()
    samples = flight.series()
    assert samples, "sampler banked nothing"
    for s in samples:
        assert {"ts", "dt_ms", "counters", "queue_depth",
                "ledger_bytes", "serving"} <= set(s)
    # the bumps landed as DELTAS in some interval, exactly once
    assert sum(s["counters"].get("serving.requests", 0)
               for s in samples) == 4
    # queue depth gauge derives from the cumulative counters
    assert samples[-1]["queue_depth"] == 3
    # a registry reset mid-window flags the sample instead of emitting
    # garbage negative deltas
    flight.sampler_start(10)
    time.sleep(0.03)
    telemetry.reset()
    time.sleep(0.05)
    flight.sampler_stop()
    flagged = [s for s in flight.series() if s.get("registry_reset")]
    assert flagged and flagged[-1]["counters"] == {}


def test_sampler_interval_zero_means_disabled():
    # MXNET_METRICS_INTERVAL_MS=0 must turn the sampler OFF, not spin
    # it at the 1 ms clamp floor
    assert flight.sampler_start(0) is None
    assert not flight.sampler_running()
    assert flight.sampler_start(-5) is None
    assert not flight.sampler_running()


def test_series_window_and_jsonl_dump(tmp_path):
    flight.sampler_start(10)
    time.sleep(0.06)
    flight.sampler_stop()
    win = flight.series_window(3)
    assert win["n"] == len(win["samples"]) <= 3
    out = str(tmp_path / "series.jsonl")
    text = flight.series_dump(out)
    lines = [json.loads(l) for l in text.splitlines()]
    assert lines == flight.series()
    with open(out) as f:
        assert f.read() == text
    flight.series_clear()
    assert flight.series() == []


# ---------------------------------------------------------------------------
# OpenMetrics endpoint
# ---------------------------------------------------------------------------

def test_openmetrics_endpoint_loopback_scrape():
    telemetry.counter_inc("serving.requests", 7)
    # two ledger contexts: the labeled gauge family must emit its
    # '# TYPE' metadata line exactly ONCE (a duplicate is invalid
    # OpenMetrics and Prometheus rejects the whole scrape).
    # SYNTHETIC ctx keys, not cpu(N): reset() deliberately preserves
    # the ledger's ALIVE map (the buffers are still alive), so real
    # device contexts carry whatever earlier tests still hold live —
    # with the native build enabled that made these exact-value
    # asserts order-dependent
    class _Buf:      # bare object() is not weakref-able
        pass

    holders = [_Buf(), _Buf()]
    telemetry.ledger_track(holders[0], "ledgertest(0)", 64)
    telemetry.ledger_track(holders[1], "ledgertest(1)", 128)
    port = flight.metrics_http_start(0)   # ephemeral, loopback-only
    try:
        body = urllib.request.urlopen(
            "http://127.0.0.1:%d/metrics" % port, timeout=10).read()
        text = body.decode()
        # every sample carries the process identity labels (ISSUE 18):
        # a fleet-scraping Prometheus can slice per rank without
        # relabel rules
        ident = telemetry.process_identity()
        who = 'host="%s",rank="%d"' % (ident["host"], ident["rank"])
        assert "# TYPE mxnet_tpu_serving_requests counter" in text
        assert "mxnet_tpu_serving_requests_total{%s} 7" % who in text
        assert "mxnet_tpu_serving_queue_depth" in text
        assert text.count(
            "# TYPE mxnet_tpu_ledger_alive_bytes gauge") == 1
        assert ('mxnet_tpu_ledger_alive_bytes{ctx="ledgertest(0)",%s}'
                ' 64' % who) in text
        assert ('mxnet_tpu_ledger_alive_bytes{ctx="ledgertest(1)",%s}'
                ' 128' % who) in text
        assert text.rstrip().endswith("# EOF")
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(
                "http://127.0.0.1:%d/secrets" % port, timeout=10)
        # idempotent: a second start reports the same bound port
        assert flight.metrics_http_start(0) == port
    finally:
        flight.metrics_http_stop()


# ---------------------------------------------------------------------------
# Postmortems
# ---------------------------------------------------------------------------

def test_postmortem_schema_and_flight_view_summary(tmp_path):
    flight.configure(str(tmp_path))
    # a synthetic request trajectory in the rings: breakdown material,
    # with explicit endpoints (sleeps of 2 and 4 ms came back 5.3 and
    # 4.1 ms on a loaded box, and the request is to hold its own wait)
    t0 = time.perf_counter_ns()
    ms = 1_000_000
    telemetry.record_span("serve_wait", t0, t0 + 2 * ms, {"req_id": 11})
    telemetry.record_span("serve_batch", t0 + 2 * ms, t0 + 3 * ms,
                          {"req_ids": [11]})
    telemetry.record_span("serve_d2h", t0 + 3 * ms, t0 + 3 * ms,
                          {"req_ids": [11]})
    telemetry.record_span("serve_request", t0, t0 + 4 * ms,
                          {"req_id": 11})
    telemetry.record_event("serving.batch", req_ids=[11], bucket=8,
                           rows=1, pad_rows=7)
    from mxnet_tpu.faults import InjectedFault
    path = flight.postmortem("unit_test", exc=InjectedFault("dispatch"),
                             extra={"req_ids": [11]})
    assert path is not None and os.path.exists(path)
    assert flight.last_postmortem() == path
    assert telemetry.counters().get("flight.postmortem") == 1
    rec = flight_view.load_dump(path)
    assert rec["reason"] == "unit_test"
    assert rec["exception"]["type"] == "InjectedFault"
    assert rec["exception"]["fault_site"] == "dispatch"
    assert rec["extra"] == {"req_ids": [11]}
    summary = flight_view.summarize(rec)
    slow = summary["slowest_requests"]
    assert slow and slow[0]["req_id"] == 11
    assert slow[0]["total_ms"] >= slow[0]["wait_ms"] > 0
    assert slow[0]["pad_rows"] == 7 and slow[0]["bucket"] == 8
    # wait/batch/d2h/resolve decompose the total
    assert slow[0]["resolve_ms"] >= 0


def test_postmortem_disabled_and_throttled(tmp_path):
    # no dir configured: triggers are no-ops
    assert flight.postmortem("nothing") is None
    flight.configure(str(tmp_path))
    p1 = flight.postmortem("flap")
    p2 = flight.postmortem("flap")          # inside the 1 s throttle
    p3 = flight.postmortem("flap", force=True)
    assert p1 is not None and p2 is None and p3 is not None
    assert p1 != p3


def test_failed_write_does_not_burn_throttle_slot(tmp_path,
                                                  monkeypatch):
    flight.configure(str(tmp_path))
    calls = {"n": 0}
    real = flight.atomic_write

    def flaky(path, data):
        calls["n"] += 1
        if calls["n"] == 1:
            raise OSError("disk full")
        return real(path, data)

    monkeypatch.setattr(flight, "atomic_write", flaky)
    assert flight.postmortem("glitch") is None       # write failed
    assert telemetry.counters().get("flight.postmortem_fail") == 1
    # the failed attempt must NOT have consumed the 1 s throttle slot:
    # an immediate re-trigger of the same reason dumps for real
    p = flight.postmortem("glitch")
    assert p is not None and os.path.exists(p)


def test_env_autostart_is_guarded():
    """Malformed MXNET_METRICS_* env values (and port 0/conflicts) must
    never break ``import mxnet_tpu`` — the recorder warns and stays
    off, like a bad MXNET_FAULTS spec."""
    env = dict(os.environ, MXNET_METRICS_INTERVAL_MS="abc",
               MXNET_METRICS_PORT="abc", JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "-c",
         "from mxnet_tpu import flight; "
         "assert not flight.sampler_running(); "
         "import mxnet_tpu.flight as f; "
         "assert f._http_server is None; print('OK')"],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        timeout=300, env=env, cwd=ROOT)
    assert proc.returncode == 0 and "OK" in proc.stdout, proc.stderr
    # the env knob treats 0 as OFF for both sampler and endpoint
    env = dict(os.environ, MXNET_METRICS_INTERVAL_MS="0",
               MXNET_METRICS_PORT="0", JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "-c",
         "from mxnet_tpu import flight; "
         "assert not flight.sampler_running(); "
         "import mxnet_tpu.flight as f; "
         "assert f._http_server is None; print('OK')"],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        timeout=300, env=env, cwd=ROOT)
    assert proc.returncode == 0 and "OK" in proc.stdout, proc.stderr


def test_thread_excepthook_writes_postmortem(tmp_path):
    flight.configure(str(tmp_path))          # also installs the hooks
    assert flight.installed()

    def _boom():
        raise RuntimeError("coalescer down")

    t = threading.Thread(target=_boom, name="doomed")
    t.start()
    t.join()
    dumps = [f for f in os.listdir(str(tmp_path))
             if "uncaught_thread_exception" in f]
    assert dumps, os.listdir(str(tmp_path))
    rec = flight_view.load_dump(os.path.join(str(tmp_path), dumps[0]))
    assert rec["exception"]["type"] == "RuntimeError"
    assert rec["extra"]["thread"] == "doomed"


def test_divergence_halt_triggers_postmortem(tmp_path):
    from mxnet_tpu.checkpoint import DivergenceError
    from mxnet_tpu.module.base_module import BaseModule
    flight.configure(str(tmp_path))
    with pytest.raises(DivergenceError):
        BaseModule()._handle_divergence("halt", None, 3, 14)
    dumps = [f for f in os.listdir(str(tmp_path))
             if "divergence" in f]
    assert dumps
    rec = flight_view.load_dump(os.path.join(str(tmp_path), dumps[0]))
    assert rec["extra"] == {"epoch": 3, "nbatch": 14, "policy": "halt"}
    # the sentinel event landed in the ring too
    assert any(e["kind"] == "divergence.detected"
               for e in rec["events"])


# ---------------------------------------------------------------------------
# flight_view CLI
# ---------------------------------------------------------------------------

def test_flight_view_cli_renders_and_rejects_garbage(tmp_path):
    flight.configure(str(tmp_path))
    telemetry.record_event("serving.shed", req_id=1, cause="coalesce")
    path = flight.postmortem("cli_test", exc=ValueError("x"))
    view = os.path.join(ROOT, "tools", "flight_view.py")
    proc = subprocess.run([sys.executable, view, path],
                         stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                         text=True, timeout=60)
    assert proc.returncode == 0, proc.stderr
    assert "flight postmortem: cli_test" in proc.stdout
    assert "event timeline" in proc.stdout
    assert "serving.shed" in proc.stdout
    proc_json = subprocess.run([sys.executable, view, path, "--json"],
                               stdout=subprocess.PIPE, text=True,
                               timeout=60)
    assert proc_json.returncode == 0
    assert json.loads(proc_json.stdout)["reason"] == "cli_test"
    # malformed inputs exit non-zero: truncated JSON, wrong schema,
    # missing file, bad usage
    bad = str(tmp_path / "bad.json")
    with open(bad, "w") as f:
        f.write("{\"schema\": \"mxnet_tpu.flight/1\", \"reason\":")
    for argv in ([view, bad], [view, str(tmp_path / "absent.json")],
                 [view]):
        p = subprocess.run([sys.executable] + argv,
                           stdout=subprocess.PIPE,
                           stderr=subprocess.PIPE, text=True, timeout=60)
        assert p.returncode != 0, argv
    wrong = str(tmp_path / "wrong.json")
    with open(wrong, "w") as f:
        json.dump({"schema": "other/1"}, f)
    with pytest.raises(flight_view.MalformedDump):
        flight_view.load_dump(wrong)


# ---------------------------------------------------------------------------
# TelemetryLogger.log_series
# ---------------------------------------------------------------------------

def test_telemetry_logger_log_series(caplog):
    logger = mx.callback.TelemetryLogger(frequent=1)
    with caplog.at_level(logging.INFO, logger="mxnet_tpu.telemetry"):
        logger.log_series()                  # no sampler: silent no-op
        flight.sampler_start(10)
        telemetry.counter_inc("serving.requests", 20)
        telemetry.counter_inc("serving.shed_requests", 5)
        telemetry.counter_inc("dispatch.serve", 2)
        time.sleep(0.08)
        flight.sampler_stop()
        logger.log_series()
        logger.log_series()                  # nothing new: no line
    lines = [r.message for r in caplog.records
             if r.message.startswith("series:")]
    assert len(lines) == 1, lines
    assert "req/s=" in lines[0] and "shed/s=" in lines[0]
    assert "dispatch/s=" in lines[0]


# ---------------------------------------------------------------------------
# Module.fit integration: step ids on the fit-phase spans
# ---------------------------------------------------------------------------

def _mlp():
    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=16, name="fc1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=4, name="fc2")
    return mx.sym.SoftmaxOutput(net, name="softmax")


def test_fit_stamps_step_ids_and_flows(monkeypatch):
    """Every span of a fit step carries its ``(epoch, nbatch)`` in the
    ring (a postmortem groups by them) AND on its profiler annotation
    (``tests/test_one_clock.py`` reads them back from a trace); the
    chrome export draws no ``step:`` flow for them any more."""
    rs = np.random.RandomState(0)
    X = rs.uniform(-1, 1, (32 * 3, 8)).astype(np.float32)
    Y = rs.randint(0, 4, 32 * 3).astype(np.float32)
    mod = mx.mod.Module(_mlp(), context=mx.cpu())
    metric = mx.metric.Accuracy()

    def fit():
        it = mx.io.NDArrayIter(X, Y, batch_size=32)
        mod.fit(it, eval_metric=metric, num_epoch=1,
                initializer=mx.initializer.Xavier(), optimizer="sgd",
                optimizer_params={"learning_rate": 0.05},
                batch_end_callback=lambda param: None)

    fit()                  # bind + compile outside the asserted window
    telemetry.reset()
    annotated = []
    orig = telemetry._annotation
    monkeypatch.setattr(
        telemetry, "_annotation",
        lambda name, ids, step: annotated.append((name, ids, step))
        or orig(name, ids, step))
    fit()
    spans = [s for s in telemetry.recent_spans()
             if s["ctx"] and s["ctx"].get("nbatch") == 1]
    names = {s["name"] for s in spans}
    assert {"fit_batch", "feed", "step_prep", "step", "step_install",
            "callbacks"} <= names, names
    assert all(s["ctx"] == {"epoch": 0, "nbatch": 1} for s in spans)
    # the same ids rode on each span's annotation; fit_batch is the step
    ann = {name: (ids, step) for name, ids, step in annotated
           if ids and ids.get("nbatch") == 1}
    assert names <= set(ann)
    assert ann["fit_batch"] == ({"epoch": 0, "nbatch": 1}, 1)
    assert ann["feed"] == ({"epoch": 0, "nbatch": 1}, None)
    assert not [e for e in telemetry.chrome_events(since_trace_start=False)
                if e.get("cat") == "flow"]
