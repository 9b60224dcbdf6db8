"""One clock (ISSUE 25): the program's spans are profiler annotations on
the device trace's clock, the fused step's ops carry scope names, set-up
and step bookkeeping have spans, and the online estimate reads the
dispatch stream. All on XLA's CPU backend: counts and names, no times."""
import contextlib
import glob
import inspect
import json
import os
import re
import threading
import time

import numpy as np
import pytest

import jax

import mxnet_tpu as mx
from mxnet_tpu import executor as _ex
from mxnet_tpu import jax_cache, telemetry

BATCH, DIM, CLASSES = 32, 8, 4
STEP_SPANS = ("feed", "step_prep", "step", "step_install")


@pytest.fixture(autouse=True)
def _clean_registry():
    telemetry.enable()
    telemetry.reset()
    yield
    telemetry.enable()
    telemetry.reset()


def _net():
    """Three op nodes: fc1 -> relu1 -> softmax."""
    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=CLASSES, name="fc1")
    net = mx.sym.Activation(net, act_type="relu", name="relu1")
    return mx.sym.SoftmaxOutput(net, name="softmax")


def _batches(n):
    rs = np.random.RandomState(0)
    X = rs.uniform(-1, 1, (BATCH * n, DIM)).astype(np.float32)
    Y = rs.randint(0, CLASSES, BATCH * n).astype(np.float32)
    return X, Y


def _fit(mod, n=3, **kwargs):
    X, Y = _batches(n)
    mod.fit(mx.io.NDArrayIter(X, Y, batch_size=BATCH),
            eval_metric=mx.metric.Accuracy(), num_epoch=1,
            initializer=mx.initializer.Xavier(), optimizer="sgd",
            optimizer_params={"learning_rate": 0.05}, **kwargs)


def _captured_builds(monkeypatch):
    """``[(program, args)]`` of every program built from here on."""
    built = []
    orig = _ex._InstrumentedProgram._build

    def _build(self, sig, args):
        built.append((self, args))
        return orig(self, sig, args)

    monkeypatch.setattr(_ex._InstrumentedProgram, "_build", _build)
    return built


def _train_step(monkeypatch):
    built = _captured_builds(monkeypatch)
    _fit(mx.mod.Module(_net(), context=mx.cpu()))
    (prog, args), = [b for b in built if b[0].kind == "train_step"]
    return prog, args


# -- B: the fused step carries names ----------------------------------------

def _op_names(compiled_text):
    return set(re.findall(r'op_name="([^"]+)"', compiled_text))


def test_train_step_module_is_named_after_its_kind(monkeypatch):
    prog, args = _train_step(monkeypatch)
    text = prog._jitted.lower(*args).compile().as_text()
    assert re.search(r"^HloModule jit_train_step\b", text, re.M), text[:200]


@pytest.mark.parametrize("needle", [
    "forward/jvp(fc1)/",              # a node of the symbol, forward
    "forward/jvp(relu1)/",
    "backward/transpose(jvp(fc1))/",  # the same node's gradient
    "transpose(",                     # how JAX spells a backward op
    "/optimizer/",
    "/metric/",
])
def test_train_step_ops_carry_phase_and_node_scopes(monkeypatch, needle):
    prog, args = _train_step(monkeypatch)
    names = _op_names(prog._jitted.lower(*args).compile().as_text())
    assert any(needle in n for n in names), sorted(names)
    assert all(n.startswith("jit(train_step)/") for n in names if "/" in n)


def test_forward_program_scopes_nodes_without_jvp(monkeypatch):
    built = _captured_builds(monkeypatch)
    mod = mx.mod.Module(_net(), context=mx.cpu())
    mod.bind(data_shapes=[("data", (BATCH, DIM))],
             label_shapes=[("softmax_label", (BATCH,))], for_training=False)
    mod.init_params()
    X, Y = _batches(1)
    mod.forward(mx.io.DataBatch([mx.nd.array(X)], [mx.nd.array(Y)]),
                is_train=False)
    (prog, args), = [b for b in built if b[0].kind == "forward"]
    text = prog._jitted.lower(*args).compile().as_text()
    assert re.search(r"^HloModule jit_forward\b", text, re.M)
    assert any("forward/fc1/" in n for n in _op_names(text))


def _stripped(hlo_text):
    """Optimised HLO's computations with every ``metadata={...}`` taken
    out (and the tables of files and stack frames the metadata indexes)."""
    body = hlo_text[hlo_text.index("\n\n\n"):]
    return re.sub(r",? ?metadata=\{[^}]*\}", "", body)


def test_scopes_change_no_program(monkeypatch):
    """The optimised HLO is the same text with and without the scopes once
    the metadata is stripped: names cost trace time, never device time."""
    prog, args = _train_step(monkeypatch)
    with_scopes = prog._jitted.lower(*args).compile().as_text()
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    prog2, args2 = _train_step(monkeypatch)
    without = prog2._jitted.lower(*args2).compile().as_text()
    assert not any("forward/" in n for n in _op_names(without))
    assert _stripped(with_scopes) == _stripped(without)


# -- A: the program's spans in the profiler's trace -------------------------

def _host_events(trace_dir):
    """``[(name, start_ns, end_ns, stats)]`` of ``/host:CPU``'s events that
    bear a telemetry span's name."""
    from jax.profiler import ProfileData
    path, = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    wanted = set(telemetry.FIT_PHASE_SPANS)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name in wanted:
                    out.append((ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns,
                                dict(ev.stats)))
    return sorted(out, key=lambda e: e[1])


def _traced_fit(tmp_path, name):
    mod = mx.mod.Module(_net(), context=mx.cpu())
    _fit(mod)                       # bind + compile outside the trace
    out = str(tmp_path / name)
    jax.profiler.start_trace(out)
    try:
        _fit(mod, batch_end_callback=lambda param: None)
    finally:
        jax.profiler.stop_trace()
    return _host_events(out)


def test_fit_batches_are_step_annotations_in_the_trace(tmp_path):
    events = _traced_fit(tmp_path, "on")
    steps = [e for e in events if e[0] == "fit_batch"]
    assert [e[3]["step_num"] for e in steps] == [0, 1, 2]
    assert [e[3]["nbatch"] for e in steps] == [0, 1, 2]
    assert all(e[3]["_r"] == 1 for e in steps)      # a step annotation
    for _, lo, hi, stats in steps:
        inside = [e for e in events
                  if e[0] != "fit_batch" and lo <= e[1] and e[2] <= hi]
        names = [e[0] for e in inside]
        for want in STEP_SPANS:
            assert names.count(want) == 1, (want, names)
        # in order, one after the other, each with its step's ids
        order = [n for n in names if n in STEP_SPANS]
        assert order == list(STEP_SPANS)
        assert all(e[3]["nbatch"] == stats["nbatch"] for e in inside)
    # the callback runs after its step, under the same ids
    cbs = [e for e in events if e[0] == "callbacks"]
    assert [e[3]["nbatch"] for e in cbs] == [0, 1, 2]
    assert all(cb[1] >= st[2] for cb, st in zip(cbs, steps))


def test_disabled_telemetry_annotates_nothing(tmp_path):
    telemetry.disable()
    assert _traced_fit(tmp_path, "off") == []


def test_cross_thread_and_retroactive_spans_stay_ring_only(monkeypatch):
    made = []
    orig = telemetry._annotation
    monkeypatch.setattr(telemetry, "_annotation",
                        lambda *a: made.append(a[0]) or orig(*a))
    with telemetry.span("same_thread"):
        pass
    sp = telemetry.span("serve_wait", ctx={"req_id": 1}).__enter__()
    t = threading.Thread(target=sp.__exit__, args=(None, None, None))
    t.start()
    t.join()
    telemetry.record_span("gate_wait", 10, 20, {"channel": "step"})
    assert made == ["same_thread"]
    ring = {s["name"] for s in telemetry.recent_spans()}
    assert ring == {"same_thread", "serve_wait", "gate_wait"}
    merged = {e["name"] for e in telemetry.chrome_events(
        since_trace_start=False, skip_annotated=True) if e["ph"] == "X"}
    assert merged == {"serve_wait", "gate_wait"}


def test_annotation_that_fails_to_arm_loses_no_span(monkeypatch):
    def boom(name, ids, step_num):
        raise RuntimeError("profiler is tearing down")

    monkeypatch.setattr(telemetry, "_annotation", boom)
    with telemetry.span("region"):
        pass
    assert telemetry.span_count("region") == 1


def test_cancelled_span_still_leaves_its_annotation(monkeypatch):
    left = []

    class Ann:
        def __enter__(self):
            return self

        def __exit__(self, *exc):
            left.append(True)

    monkeypatch.setattr(telemetry, "_annotation", lambda *a: Ann())
    with telemetry.span("io_next") as sp:
        sp.cancel()
    assert left == [True] and telemetry.span_count("io_next") == 0


# -- A: the merged chrome dump ----------------------------------------------

def test_profiler_dump_writes_each_span_once_and_links_requests(tmp_path):
    """``mx.profiler`` run/stop: a same-thread span is in the dump once
    (the profiler's own slice, its ids as args), a serving request's
    cross-thread chain is merged from the ring with its flow."""
    fname = str(tmp_path / "profile.json")
    mx.profiler.set_config(filename=fname)
    mx.profiler.set_state("run")
    with telemetry.causal(epoch=0, nbatch=5):
        with telemetry.span("feed"):
            mx.nd.dot(mx.nd.ones((8, 8)), mx.nd.ones((8, 8))).wait_to_read()
    wait = telemetry.span("serve_wait", ctx={"req_id": 7}).__enter__()
    req = telemetry.span("serve_request", ctx={"req_id": 7}).__enter__()

    def _resolver():
        wait.__exit__(None, None, None)
        with telemetry.span("serve_batch", ctx={"req_ids": [7]}):
            pass
        req.__exit__(None, None, None)

    t = threading.Thread(target=_resolver)
    t.start()
    t.join()
    telemetry.record_program({"id": "fake/s0", "kind": "forward"})
    mx.profiler.set_state("stop")
    with open(fname) as f:
        trace = json.load(f)
    events = trace["traceEvents"]
    feed = [e for e in events if e.get("ph") == "X" and e["name"] == "feed"]
    assert len(feed) == 1 and feed[0].get("cat") != "host"
    assert {k: int(v) for k, v in feed[0]["args"].items()} \
        == {"epoch": 0, "nbatch": 5}
    merged = [e["name"] for e in events if e.get("cat") == "host"]
    assert sorted(merged) == ["serve_batch", "serve_request", "serve_wait"]
    flow = [e["ph"] for e in events
            if e.get("cat") == "flow" and e["id"] == "req:7"]
    assert flow == ["s", "t", "f"]
    assert not [e for e in events if e.get("cat") == "flow"
                and str(e["id"]).startswith("step:")]
    # the merged slices sit on the device dump's clock, inside its span
    stamps = [e["ts"] for e in events
              if e.get("ph") == "X" and e.get("cat") != "host"]
    for e in events:
        if e.get("cat") == "host":
            assert min(stamps) <= e["ts"] <= max(stamps) + 60e6
    assert list(trace["otherData"]["mxnet_tpu_programs"]) == ["fake/s0"]


# -- C: spans where set-up and bookkeeping happen ---------------------------

@pytest.mark.parametrize("name", telemetry.SETUP_SPANS)
def test_setup_spans_show_in_span_seconds(name):
    assert telemetry.span_seconds(name) == 0.0
    _fit(mx.mod.Module(_net(), context=mx.cpu()))
    assert telemetry.span_count(name) == 1
    assert telemetry.span_seconds(name) > 0.0


def test_setup_spans_skip_calls_that_do_nothing():
    mod = mx.mod.Module(_net(), context=mx.cpu())
    _fit(mod)
    _fit(mod)       # bound and initialised: the second fit sets nothing up
    assert {n: telemetry.span_count(n) for n in telemetry.SETUP_SPANS} \
        == {"fit_setup": 2, "bind": 1, "init_params": 1,
            "init_optimizer": 1}


# -- set-up measured from inside the program --------------------------------

BUILD_PHASES = ("jit_trace", "jit_lower", "jit_compile", "jit_card")
SETUP_INNER = ("bind", "init_params", "init_optimizer")


def _ring():
    """``[(name, start_s, end_s)]`` of the span ring."""
    return [(s["name"], s["ts"], s["ts"] + s["dur_ms"] / 1e3)
            for s in telemetry.recent_spans()]


def _inside(spans, outer, slack=2e-6):
    """The names of the spans that lie inside ``outer``'s interval (the
    ring stamps to the microsecond)."""
    _, lo, hi = outer
    return sorted(n for n, a, b in spans if (n, a, b) != outer
                  and lo - slack <= a and b <= hi + slack)


def _fit_setup_nests_the_set_up_spans():
    _fit(mx.mod.Module(_net(), context=mx.cpu()))
    spans = _ring()
    setup, = [s for s in spans if s[0] == "fit_setup"]
    inner = _inside(spans, setup)
    assert [n for n in inner if n in SETUP_INNER] == sorted(SETUP_INNER)
    assert not [n for n in inner if n == "fit_batch"]
    first = min(s[1] for s in spans if s[0] == "fit_batch")
    assert setup[2] <= first + 2e-6        # it ends at the first batch


def _jit_build_nests_each_phase_once():
    _fit(mx.mod.Module(_net(), context=mx.cpu()))
    spans = _ring()
    builds = [s for s in spans if s[0] == "jit_build"]
    assert builds and len(builds) == telemetry.span_count("jit_build")
    for build in builds:
        assert [n for n in _inside(spans, build) if n.startswith("jit_")] \
            == sorted(BUILD_PHASES)
    card, = [c for c in telemetry.programs().values()
             if c["kind"] == "train_step"]
    assert all(card[k] >= 0 for k in ("trace_ms", "lower_ms", "compile_ms",
                                       "card_ms"))
    # the step's XLA build is logged as the instrumented build's
    step, = [e["data"] for e in telemetry.events()
             if e["kind"] == "xla_build" and "train_step" in e["data"]["fn"]]
    assert step["instrumented"] is True


def _second_fit_builds_nothing():
    mod = mx.mod.Module(_net(), context=mx.cpu())
    _fit(mod)
    telemetry.reset()
    _fit(mod)
    assert telemetry.span_count("fit_setup") == 1
    assert not [n for n in telemetry.span_stats() if n.startswith("jit_")]


def _epoch_sync_covers_the_counters_fetch(monkeypatch):
    fetched = []
    orig = mx.mod.Module._publish_aux_counters

    def publish(self):
        fetched.append(telemetry.epoch_s(time.perf_counter()))
        return orig(self)

    monkeypatch.setattr(mx.mod.Module, "_publish_aux_counters", publish)
    _fit(mx.mod.Module(_net(), context=mx.cpu()))
    syncs = [s for s in _ring() if s[0] == "epoch_sync"]
    assert len(fetched) == 1 and len(syncs) == 1
    assert syncs[0][1] - 2e-6 <= fetched[0] <= syncs[0][2] + 2e-6


def _profiler_shows_the_same_nesting(tmp_path):
    from jax.profiler import ProfileData
    out = str(tmp_path / "trace")
    jax.profiler.start_trace(out)
    try:
        _fit(mx.mod.Module(_net(), context=mx.cpu()))
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(out, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    wanted = set(telemetry.SETUP_SPANS) | set(telemetry.COMPILE_SPANS)
    spans = [(ev.name, ev.start_ns * 1e-9,
              (ev.start_ns + ev.duration_ns) * 1e-9)
             for plane in ProfileData.from_file(path).planes
             if plane.name == "/host:CPU"
             for line in plane.lines for ev in line.events
             if ev.name in wanted]
    setup, = [s for s in spans if s[0] == "fit_setup"]
    assert [n for n in _inside(spans, setup, 0) if n in SETUP_INNER] \
        == sorted(SETUP_INNER)
    for build in [s for s in spans if s[0] == "jit_build"]:
        assert _inside(spans, build, 0) == sorted(BUILD_PHASES)


def _one_listener_however_many_watches():
    from jax._src import monitoring     # the registry's readers
    watches = [jax_cache.CacheWatch() for _ in range(3)]
    jax_cache.listen()
    assert monitoring.get_event_listeners().count(jax_cache._on_event) == 1
    assert monitoring.get_event_duration_listeners().count(
        jax_cache._on_duration) == 1
    assert all(w.counts() == {"requests": 0, "hits": 0, "misses": 0}
               for w in watches)


def _cache_watch_counts_with_telemetry_off():
    telemetry.disable()
    watch = jax_cache.CacheWatch()
    jax.monitoring.record_event(
        "/jax/compilation_cache/compile_requests_use_cache")
    jax.monitoring.record_event("/jax/compilation_cache/cache_misses")
    assert watch.counts() == {"requests": 1, "hits": 0, "misses": 1}
    assert telemetry.events() == []


def _fresh_jit_is_one_build_outside():
    def triple_plus_one(x):
        return x * 3.0 + 1.0

    x = np.ones((5,), np.float32)
    jax.jit(triple_plus_one)(x).block_until_ready()
    builds = [e["data"] for e in telemetry.events()
              if e["kind"] == "xla_build"]
    assert len(builds) == 1, builds
    assert "triple_plus_one" in builds[0]["fn"]
    assert builds[0]["instrumented"] is False
    assert builds[0]["cache_hit"] is False and builds[0]["s"] > 0
    counts = telemetry.counters()
    assert counts["xla.programs"] == counts["xla.cache_misses"] == 1
    assert counts["xla.build_s"] == builds[0]["s"]


def _cache_hit_marks_its_build():
    jax.monitoring.record_event("/jax/compilation_cache/cache_hits")
    jax.monitoring.record_event_duration_secs(
        "/jax/core/compile/backend_compile_duration", 0.25, fun_name="f")
    jax.monitoring.record_event_duration_secs(
        "/jax/core/compile/jaxpr_trace_duration", 0.5, fun_name="f")
    assert [e["data"] for e in telemetry.events()] == [
        {"fn": "f", "s": 0.25, "cache_hit": True, "instrumented": False}]
    assert "xla.cache_misses" not in telemetry.counters()


@pytest.mark.parametrize("check", [
    _fit_setup_nests_the_set_up_spans,
    _jit_build_nests_each_phase_once,
    _second_fit_builds_nothing,
    _epoch_sync_covers_the_counters_fetch,
    _profiler_shows_the_same_nesting,
    _one_listener_however_many_watches,
    _cache_watch_counts_with_telemetry_off,
    _fresh_jit_is_one_build_outside,
    _cache_hit_marks_its_build,
], ids=lambda f: f.__name__.strip("_"))
def test_set_up_is_spanned_and_builds_are_logged(check, monkeypatch,
                                                 tmp_path):
    wants = inspect.signature(check).parameters
    check(**{k: v for k, v in (("monkeypatch", monkeypatch),
                               ("tmp_path", tmp_path)) if k in wants})


def test_fused_batches_transfer_exactly_their_bytes():
    """Three fused batches of a known numpy batch add its bytes to
    ``transfer.h2d_bytes`` each, and nothing else."""
    mod = mx.mod.Module(_net(), context=mx.cpu())
    _fit(mod)
    X, Y = _batches(1)
    batch = mx.io.DataBatch([X], [Y])
    metric = mx.metric.Accuracy()
    before = telemetry.counters().get("transfer.h2d_bytes", 0)
    for i in range(3):
        assert mod._fused_batch_step(batch, metric)
        assert telemetry.counters()["transfer.h2d_bytes"] - before \
            == (i + 1) * (X.nbytes + Y.nbytes)
    assert telemetry.span_count("step_prep") \
        == telemetry.span_count("step_install") \
        == telemetry.span_count("step")


# -- the online estimate reads the dispatch stream --------------------------

def test_online_rate_is_over_dispatch_wall_time(monkeypatch):
    """A fake card and an enqueue that returns at once: the rate is the
    FLOPs dispatched after the first dispatch over the wall time since
    it, however short the ``step`` spans were."""
    clock = iter(range(0, 10 ** 12, 10 ** 7))       # 10 ms a reading
    monkeypatch.setattr(telemetry.time, "perf_counter_ns",
                        lambda: next(clock))
    card = {"id": "fake/s0", "kind": "train_step", "flops": 2e9}
    telemetry.record_program(card)
    for _ in range(11):
        telemetry.program_dispatch(card)
        telemetry._record_span("step", 0, 1000)     # a 1 us enqueue
    telemetry.set_peak_flops(1e12)
    try:
        online = telemetry.snapshot()["online"]
    finally:
        telemetry.set_peak_flops(None)
    assert online["flops_dispatched"] == 11 * 2e9
    assert online["step_time_s"] == pytest.approx(11e-6)
    assert online["dispatch_wall_s"] == pytest.approx(0.1)
    assert online["model_flops_per_s"] == pytest.approx(10 * 2e9 / 0.1)
    assert online["mfu"] == pytest.approx(0.2)      # not 11 * 2e9 / 11e-6
    telemetry.reset()
    assert telemetry.online()["model_flops_per_s"] is None
