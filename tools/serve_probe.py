#!/usr/bin/env python3
"""The count-only tier-1 lanes of the serving path: the micro-batching
engine, the persisted compile cache, overload control, the flight
recorder and the continuous-batching decode engine.

Every lane runs on XLA's CPU backend, prints ONE JSON object (and writes
it to ``--json-out``) and exits 0 whenever it ran to the end: counts,
bytes, equality and the guarantees the engine itself promises, never a
rate or a ratio of two clocks. ``tests/test_serving_lane.py`` and
``tests/test_decode_lane.py`` hold each property as a test of its own.
A rate is read on the chip by ``benchmarks/run.py`` and nowhere else.

Serve-smoke lane:   python tools/serve_probe.py --serve-smoke \
                        [--json-out PATH]
  (tiny MLP, 256 one-row requests through ``serving.InferenceEngine``:
  one compiled program per bucket signature via
  ``telemetry.programs()``, zero ``jit_compile`` spans in the window,
  and the window's dispatches against the requests it served)

Warm-smoke lane:    python tools/serve_probe.py --warm-smoke \
                        [--json-out PATH]
  (the PERSISTED compile cache, ISSUE 6: two fresh processes construct
  the same serving engine over one shared ``MXNET_COMPILE_CACHE`` dir.
  The first (cold) compiles and stores every bucket program; the second
  (warm) is to register ZERO ``jit_compile`` spans and a deserialize
  hit for every bucket, and to answer the probe request with the cold
  leg's bits.)

Chaos-smoke lane:   python tools/serve_probe.py --chaos-smoke \
                        [--json-out PATH]
  (the OVERLOAD-CONTROL path, ISSUE 7: the engine runs an open-loop
  offered-load ladder up to 2x the capacity it shows under
  ``MXNET_FAULTS``-style injected dispatch faults (a per-dispatch
  delay throttling capacity + probabilistic raises exercising the
  retry budget), with a bounded admission queue and per-request
  deadlines. Reported: hung futures, shed counters at 2x offered load,
  admitted-request p99 beside the deadline the engine promised, and the
  injected-fault telemetry counter beside the registry's fire count.)

Postmortem-smoke lane:  python tools/serve_probe.py --postmortem-smoke \
                            [--json-out PATH]
  (the FLIGHT RECORDER, ISSUE 10: closed-loop waves run with the
  metrics sampler on after an injected TERMINAL dispatch fault
  (``dispatch:raise:first=K`` outlasting the retry budget, so one
  batch fails for good). Reported: the postmortem JSON in the flight
  dir as ``tools/flight_view.py`` parses it (and whether it REJECTS a
  corrupted copy), the fault site and the dying batch's member req_ids
  the dump names, the sampler's time-series window, and what the
  recorder did in the waves' window: spans and events by name, beside
  the requests and batches they were recorded for.)

Decode-smoke lane:  python tools/serve_probe.py --decode-smoke \
                        [--json-out PATH]
  (the continuous-batching decode engine; see ``decode_smoke``)
"""
import json
import os
import shutil
import subprocess
import sys
import tempfile
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from lane_common import emit, force_cpu_devices, main

DEC_MP = 8                         # mp-sharded KV-cache leg mesh width

if "--decode-smoke" in sys.argv:
    force_cpu_devices(DEC_MP)      # the decode lane's mp leg needs the mesh

import numpy as np
import jax

jax.config.update("jax_platforms", "cpu")

import mxnet_tpu as mx
from mxnet_tpu import telemetry
from mxnet_tpu.serving import InferenceEngine

D, C, HID = 16, 4, 64
N_REQ = 256
MAX_BATCH = 16

# warm-smoke model: deep enough that every bucket is a program worth
# persisting; the fixed startup work (bind, shape inference, rng key)
# is identical across the legs
WARM_LAYERS, WARM_HID, WARM_D = 32, 192, 32
WARM_MAX_BATCH = 32


def _mlp():
    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=HID, name="fc1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=C, name="fc2")
    return mx.sym.SoftmaxOutput(net, name="softmax")


def _params(symbol):
    rng = np.random.RandomState(0)
    shapes, _, _ = symbol.infer_shape_partial(data=(2, D))
    return {"arg:" + n: mx.nd.array(rng.normal(0, 0.1, s)
                                    .astype(np.float32))
            for n, s in zip(symbol.list_arguments(), shapes)
            if n not in ("data", "softmax_label")}


def serve_smoke(json_out=None, n_req=N_REQ):
    sym = _mlp()
    params = _params(sym)
    rng = np.random.RandomState(1)
    reqs = [rng.normal(size=(1, D)).astype(np.float32)
            for _ in range(n_req)]
    # a coalescing deadline no burst reaches: a batch goes out when it
    # is FULL (and the flush sends the rest), so how the burst forms
    # batches does not depend on how fast this host submits it
    engine = InferenceEngine(sym, params, {"data": (1, D)},
                             max_batch=MAX_BATCH, max_wait_ms=60e3,
                             max_inflight=4)
    # the bucket cache as warmup built it, captured BEFORE the window
    # (telemetry.reset() clears the registry; cards re-register on
    # dispatch, so the registry afterwards only shows the buckets the
    # window happened to use)
    programs = sorted(engine.program_cards())
    was_enabled = telemetry.enabled()
    telemetry.enable()
    try:
        telemetry.reset()
        futs = [engine.submit(data=x) for x in reqs]
        engine.flush()
        for f in futs:
            f.result(timeout=300)
        snap = telemetry.snapshot()
    finally:
        if not was_enabled:
            telemetry.disable()
    out = {
        "lane": "serve_smoke",
        "n_requests": n_req,
        "max_batch": MAX_BATCH,
        "buckets": engine.buckets,
        "programs": programs,
        "telemetry": {
            "counters": {k: v for k, v in snap["counters"].items()
                         if k.startswith(("serving.", "dispatch."))},
            # _InstrumentedProgram._build times every program build as
            # a jit_compile span: the engine dispatch path never
            # touches the jit.compile COUNTER (that counts
            # _GraphProgram entry-point lookups), so the span count is
            # the one signal that catches a recompile inside the window
            "jit_compiles": snap["spans"].get(
                "jit_compile", {}).get("count", 0),
        },
    }
    engine.close()
    return emit(out, json_out)


def _warm_mlp():
    data = mx.sym.Variable("data")
    net = data
    for i in range(WARM_LAYERS):
        net = mx.sym.FullyConnected(net, num_hidden=WARM_HID,
                                    name="wfc%d" % i)
        net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=C, name="whead")
    return mx.sym.SoftmaxOutput(net, name="softmax")


def warm_child():
    """One process's leg of the warm-smoke pair: construct (and warm up)
    the serving engine over the ambient ``MXNET_COMPILE_CACHE``, serve
    a fixed probe request, and report the compile-tier spans and the
    cache's counters. Cold or warm is decided entirely by what the
    cache dir already holds."""
    sym = _warm_mlp()
    rng = np.random.RandomState(0)
    shapes, _, _ = sym.infer_shape_partial(data=(2, WARM_D))
    params = {"arg:" + n: mx.nd.array(rng.normal(0, 0.05, s)
                                      .astype(np.float32))
              for n, s in zip(sym.list_arguments(), shapes)
              if n not in ("data", "softmax_label")}
    probe_req = rng.normal(size=(1, WARM_D)).astype(np.float32)
    telemetry.enable()
    telemetry.reset()
    engine = InferenceEngine(sym, params, {"data": (1, WARM_D)},
                             max_batch=WARM_MAX_BATCH, max_wait_ms=1.0,
                             max_inflight=4)
    outs = engine.submit(data=probe_req).result(timeout=120)
    snap = telemetry.snapshot()
    spans = {k: snap["spans"].get(k, {}).get("count", 0)
             for k in telemetry.COMPILE_SPANS}
    out = {
        "lane": "warm_child",
        "buckets": engine.buckets,
        "jit_compile_spans": spans["jit_compile"],
        "jit_deserialize_spans": spans["jit_deserialize"],
        "compile_cache": {k: v for k, v in snap["counters"].items()
                          if k.startswith("compile_cache.")},
        "sources": sorted({c.get("source") for c in
                           snap["programs"].values() if c.get("source")}),
        # bit-exactness probe: the warm (deserialized) leg must produce
        # exactly what the cold (compiled) leg produced
        "probe_sum": float(np.float64(outs[0].astype(np.float64).sum())),
    }
    engine.close()
    print(json.dumps(out), flush=True)
    return out


def warm_smoke(json_out=None):
    """The warm-start lane (ISSUE 6): two FRESH processes over one
    shared compile-cache dir. Process 1 (cold) populates the store;
    process 2 (warm) is to skip XLA entirely (zero ``jit_compile``
    spans, a deserialize hit for every bucket) and to match the cold
    leg's output bit for bit."""
    cache = tempfile.mkdtemp(prefix="mxtpu_warm_smoke_cc_")
    legs = {}
    try:
        for leg in ("cold", "warm"):
            env = dict(os.environ, JAX_PLATFORMS="cpu",
                       MXNET_COMPILE_CACHE=cache)
            env.pop("XLA_FLAGS", None)       # single-device lane
            proc = subprocess.run(
                [sys.executable, os.path.abspath(__file__),
                 "--warm-child"],
                stdout=subprocess.PIPE, text=True, timeout=420, env=env)
            parsed = None
            for line in reversed(proc.stdout.splitlines()):
                if line.strip().startswith("{"):
                    parsed = json.loads(line)
                    break
            if proc.returncode != 0 or parsed is None:
                raise SystemExit("warm-smoke %s child failed (rc %d): %s"
                                 % (leg, proc.returncode,
                                    proc.stdout[-2000:]))
            legs[leg] = parsed
    finally:
        shutil.rmtree(cache, ignore_errors=True)
    return emit({
        "lane": "warm_smoke",
        "n_buckets": len(legs["cold"]["buckets"]),
        "cold": legs["cold"],
        "warm": legs["warm"],
    }, json_out)


# chaos-smoke knobs: the injected per-dispatch DELAY throttles the CPU
# lane's capacity to something an open-loop schedule can actually
# overload inside a CI window; the RAISE probability exercises the
# retry budget; the bounded queue + deadline are what 2x offered load
# then slams into
CHAOS_DELAY_MS = 4.0
CHAOS_RAISE_P = 0.12
CHAOS_SEED = 11
CHAOS_DEADLINE_MS = 150.0
CHAOS_QUEUE_ROWS = 48
CHAOS_N_REQ = 384
CHAOS_SPEC = "dispatch:delay=%g" % CHAOS_DELAY_MS
CHAOS_SPEC_FAULTY = CHAOS_SPEC + \
    ";dispatch:raise:p=%g,seed=%d" % (CHAOS_RAISE_P, CHAOS_SEED)


def chaos_smoke(json_out=None, n_req=CHAOS_N_REQ):
    """The fault-tolerant-serving lane (ISSUE 7). What it reports are
    guarantees and not rates: every future resolves, the queue stays
    inside its bound, an admitted request keeps the deadline the engine
    promised it, and every injected fault is accounted for."""
    from mxnet_tpu import faults
    from mxnet_tpu.serving import (DeadlineExceeded, QueueOverflow,
                                   CircuitOpen)
    sym = _mlp()
    params = _params(sym)
    rng = np.random.RandomState(1)
    reqs = [rng.normal(size=(1, D)).astype(np.float32)
            for _ in range(64)]
    telemetry.enable()
    engine = InferenceEngine(
        sym, params, {"data": (1, D)}, max_batch=MAX_BATCH,
        max_wait_ms=1.0, max_inflight=4,
        max_queue_rows=CHAOS_QUEUE_ROWS,
        deadline_ms=CHAOS_DEADLINE_MS, overload="shed",
        retry_budget=2, retry_backoff_ms=1.0,
        breaker_threshold=50)          # tripping would mask the ladder
    out = {
        "lane": "chaos_smoke",
        "deadline_ms": CHAOS_DEADLINE_MS,
        "max_queue_rows": CHAOS_QUEUE_ROWS,
    }
    try:
        # capacity under the injected dispatch DELAY (the throttle is
        # part of the chaos environment, so the ladder's fractions are
        # fractions of the environment's real capacity)
        faults.configure(CHAOS_SPEC)
        t0 = time.perf_counter()
        done = 0
        while done < n_req // 2:
            # closed-loop waves under the admission bound: capacity is
            # what the throttled engine sustains, measured without
            # tripping the very shedding the ladder exists to test
            wave = min(CHAOS_QUEUE_ROWS // 2, n_req // 2 - done)
            futs = [engine.submit(data=reqs[i % len(reqs)])
                    for i in range(wave)]
            engine.flush()
            for f in futs:
                f.result(timeout=120)
            done += wave
        # the ladder needs the capacity this host shows to offer twice
        # it: a number of this CPU run that stays in here
        capacity = done / (time.perf_counter() - t0)

        # open-loop ladder with raises on top of the delay; latency is
        # measured from the SCHEDULED arrival (coordinated-omission-
        # free), admission sheds raise synchronously at submit. The
        # rung at capacity is the ramp: the engine meets twice its
        # capacity from a working queue, and that rung is the one
        # reported
        faults.configure(CHAOS_SPEC_FAULTY)
        for frac in (1.0, 2.0):
            faults.reset_counts()
            telemetry.reset()
            rate = capacity * frac
            pend, lats = [], []
            admission_shed = 0
            t0 = time.perf_counter()
            for i in range(n_req):
                sched = t0 + i / rate
                now = time.perf_counter()
                if sched > now:
                    time.sleep(sched - now)
                try:
                    fut = engine.submit(data=reqs[i % len(reqs)])
                except (QueueOverflow, CircuitOpen):
                    admission_shed += 1
                    continue
                fut.add_done_callback(
                    lambda f, s=sched: lats.append(
                        (time.perf_counter() - s) * 1e3)
                    if not f.exception() else None)
                pend.append(fut)
            engine.flush()
            ok = shed = failed = hung = 0
            for fut in pend:
                try:
                    fut.result(timeout=120)
                    ok += 1
                except DeadlineExceeded:
                    shed += 1
                except Exception:
                    failed += 1
            hung = sum(0 if f.done() else 1 for f in pend)
        lats.sort()
        out["at_twice_capacity"] = {
            "submitted": len(pend),
            "ok": ok,
            "shed_admission": admission_shed,
            "shed_deadline": shed,
            "failed": failed,
            "hung": hung,
            # read beside deadline_ms, which the engine promised every
            # request it admitted
            "admitted_p99_ms": round(
                telemetry._percentile(lats, 99), 3) if lats else None,
            "faults_fired": faults.counts().get("dispatch", {}).get(
                "fired", 0),
            "faults_injected_counter": telemetry.counters().get(
                "faults.injected.dispatch", 0),
            "queued_rows": engine.stats()["queued_rows"],
        }
    finally:
        faults.clear()
        engine.close()
    out["shed_requests"] = engine.stats()["shed_requests"]
    return emit(out, json_out)


# postmortem-smoke knobs: the raise rule must outlast the retry budget
# on ONE batch (initial attempt + retry_budget retries all land inside
# first=K) so the failure is TERMINAL; the delay keeps the waves' batches
# in flight long enough for the sampler to see them
PM_RETRY_BUDGET = 1
PM_RAISE_FIRST = PM_RETRY_BUDGET + 2     # every attempt of batch 1 + slack
PM_SPEC_TERMINAL = "%s;dispatch:raise:first=%d" % (CHAOS_SPEC,
                                                   PM_RAISE_FIRST)
PM_SAMPLER_MS = 25.0
PM_N_REQ = 192


def postmortem_smoke(json_out=None, n_req=PM_N_REQ):
    """The flight-recorder lane (ISSUE 10)."""
    from mxnet_tpu import faults, flight
    sym = _mlp()
    params = _params(sym)
    rng = np.random.RandomState(1)
    reqs = [rng.normal(size=(1, D)).astype(np.float32)
            for _ in range(64)]
    # the dump outlives the probe beside its JSON: the lane's test runs
    # the flight_view CLI over it
    fdir = os.path.join(os.path.dirname(os.path.abspath(json_out)),
                        "flight") if json_out \
        else tempfile.mkdtemp(prefix="mxtpu_pm_smoke_flight_")
    os.makedirs(fdir, exist_ok=True)
    telemetry.enable()
    telemetry.reset()
    flight.configure(fdir)
    flight.series_clear()
    flight.sampler_start(PM_SAMPLER_MS)
    out = {"lane": "postmortem_smoke"}
    engine = InferenceEngine(
        sym, params, {"data": (1, D)}, max_batch=MAX_BATCH,
        max_wait_ms=1.0, max_inflight=4,
        max_queue_rows=CHAOS_QUEUE_ROWS,
        deadline_ms=CHAOS_DEADLINE_MS, overload="shed",
        retry_budget=PM_RETRY_BUDGET, retry_backoff_ms=1.0,
        breaker_threshold=0)       # the TERMINAL failure is the story,
                                   # not a breaker fast-fail masking it
    try:
        # phase 1: the terminal fault: one batch's every attempt
        # raises, its futures fail, the flight recorder dumps
        faults.configure(PM_SPEC_TERMINAL)
        doomed = [engine.submit(data=reqs[i % len(reqs)])
                  for i in range(6)]
        engine.flush()
        failed_rids = []
        for f in doomed:
            try:
                f.result(timeout=120)
            except Exception:
                failed_rids.append(f.req_id)
        out["failed_requests"] = len(failed_rids)
        out["failed_req_ids"] = sorted(failed_rids)

        # phase 2: closed-loop waves under the delay throttle (faults
        # still active minus the spent raise rule), in a registry window
        # of their own: zero hung futures says the recorder added no
        # stall, and the window's spans and events, beside the requests
        # and batches they were recorded for, say what the recorder
        # does for a request. The engine fails a dying batch's futures
        # BEFORE it dumps, so wait for phase 1's dump: it reads the
        # registry that the new window clears
        for _ in range(3000):
            if flight.last_postmortem() is not None:
                break
            time.sleep(0.01)
        faults.configure(CHAOS_SPEC)
        telemetry.reset()
        hung = 0
        done = 0
        while done < n_req:
            wave = min(CHAOS_QUEUE_ROWS // 2, n_req - done)
            futs = [engine.submit(data=reqs[i % len(reqs)])
                    for i in range(wave)]
            engine.flush()
            for f in futs:
                try:
                    f.result(timeout=120)
                except Exception:
                    pass
                if not f.done():
                    hung += 1
            done += wave
        out["hung"] = hung
        events = {}
        for ev in telemetry.events():
            events[ev["kind"]] = events.get(ev["kind"], 0) + 1
        out["recorder"] = {
            "requests": done,
            "counters": {k: v for k, v in telemetry.counters().items()
                         if k.startswith("serving.")},
            "span_counts": {k: v["count"]
                            for k, v in telemetry.span_stats().items()},
            "event_counts": events,
        }
    finally:
        faults.clear()
        flight.sampler_stop()
        engine.close()
        flight.configure(None)

    out["series_window"] = flight.series_window(60)
    pm_path = flight.last_postmortem()
    out["postmortem_path"] = pm_path

    view = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "flight_view.py")

    def run_view(path, extra=()):
        return subprocess.run(
            [sys.executable, view, path, *extra],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            text=True, timeout=60)

    out["view_rc"] = out["view_summary"] = out["corrupt_view_rc"] = None
    if pm_path is not None and os.path.exists(pm_path):
        # the terminal fault's postmortem as flight_view PARSES it
        proc = run_view(pm_path, ("--json",))
        out["view_rc"] = proc.returncode
        if proc.returncode == 0:
            summary = json.loads(proc.stdout)
            out["view_summary"] = {k: summary.get(k) for k in
                                   ("reason", "exception", "extra")}
        # a corrupted dump is to be REJECTED non-zero
        bad = pm_path + ".corrupt"
        with open(pm_path) as f:
            with open(bad, "w") as g:
                g.write(f.read()[:200])   # truncated JSON
        out["corrupt_view_rc"] = run_view(bad).returncode
        os.unlink(bad)
    return emit(out, json_out)


# decode-smoke knobs: the continuous-batching decode engine
# (mxnet_tpu/decode.py). The workload skews generation lengths (1 long
# per wave of 8) because that skew is WHY continuous batching exists: a
# wave-synchronized static whole-batch decoder pays the longest member's
# steps for every wave while finished lanes idle; slot-level admission
# keeps the pool full, so the same tokens take fewer decode dispatches.
DEC_SLOTS = 8
DEC_WAVES = 6
DEC_SHORT, DEC_LONG = 4, 40        # generated tokens per sequence kind
DEC_PROMPT = 4


def _decode_cell(heads=8):
    from mxnet_tpu.decode import AttentionDecodeCell
    return AttentionDecodeCell(vocab=256, embed=64, heads=heads,
                               head_dim=16, max_len=64)


def decode_smoke(json_out=None):
    """Continuous-batching decode lane (tier-1 CI), three legs:

    * equality: slot-batched decode against one-at-a-time decode
      through the same engine: tokens and arg-max compared exactly, and
      of the float32 logits (two programs of different slot-bucket
      width) the largest gap beside the largest magnitude, for the
      lane's test to hold to ``tests/helpers.py``'s tolerance;
    * schedule: a skewed-length stream queued up front through the
      continuous engine, in one registry window: ``jit_compile`` spans
      (warmup built every prompt-length and slot-count bucket program
      up front), ``decode.steps`` and ``decode.tokens``, and beside
      them the step count of the static whole-batch schedule of the
      same work, COMPUTED as waves x longest generation;
    * mp-sharded KV cache: under ``DECODE_PARTITION_RULES`` on a
      1x{mp} mesh, the cache pool's committed ledger bytes beside the
      same pool replicated onto that mesh.
    """
    from mxnet_tpu.decode import DecodeEngine
    from mxnet_tpu.parallel.ring_attention import DECODE_PARTITION_RULES

    rng = np.random.RandomState(0)
    out = {
        "lane": "decode_smoke",
        "devices": jax.device_count(),
        "slots": DEC_SLOTS,
        "waves": DEC_WAVES,
        "gen_long": DEC_LONG,
    }

    def prompt():
        return rng.randint(1, 255, DEC_PROMPT).astype(np.int32)

    # -- leg 1: slot-batched vs one-at-a-time -------------------------------
    cell = _decode_cell()
    eng = DecodeEngine(cell, cell.init_params(1), slots=4,
                       max_prompt_len=8, max_new_tokens=8,
                       keep_logits=True)
    probes = [prompt() for _ in range(4)]
    serial = [eng.generate(p) for p in probes]
    batched = [f.result(timeout=300)
               for f in [eng.submit(p) for p in probes]]
    eng.close()
    pairs = [(np.asarray(a.logits, np.float64),
              np.asarray(b.logits, np.float64))
             for a, b in zip(serial, batched)]
    out["equality"] = {
        "tokens_equal": all(a.tokens == b.tokens
                            for a, b in zip(serial, batched)),
        "argmax_equal": all(np.array_equal(a.argmax(-1), b.argmax(-1))
                            for a, b in pairs),
        "logits_max_abs_diff": max(float(np.abs(a - b).max())
                                   for a, b in pairs),
        "logits_max_abs": max(float(np.abs(a).max()) for a, _ in pairs),
    }

    # -- leg 2: the continuous schedule, counted ----------------------------
    eng = DecodeEngine(cell, cell.init_params(1), slots=DEC_SLOTS,
                       max_prompt_len=8, max_new_tokens=DEC_LONG)
    # one wave = a slot pool's worth of sequences, one long member;
    # submitted longs first, so their long tails overlap the short
    # churn instead of trailing an empty pool
    stream = sorted(((prompt(), DEC_LONG if s == 0 else DEC_SHORT)
                     for _ in range(DEC_WAVES) for s in range(DEC_SLOTS)),
                    key=lambda seq: -seq[1])
    was_enabled = telemetry.enabled()
    telemetry.enable()
    try:
        telemetry.reset()
        # every sequence queued up front; per-step slot admission keeps
        # the pool full until the work runs dry
        futs = [eng.submit(p, max_new_tokens=n) for p, n in stream]
        for f in futs:
            f.result(timeout=300)
        snap = telemetry.snapshot()
    finally:
        if not was_enabled:
            telemetry.disable()
    eng.close()
    out.update({
        "total_tokens": sum(n for _, n in stream),
        # a static whole-batch decoder admits the next wave only when
        # the whole previous wave finished: the longest member's steps
        # for every wave
        "static_schedule_steps": DEC_WAVES * DEC_LONG,
        "jit_compiles_in_window": snap["spans"].get(
            "jit_compile", {}).get("count", 0),
        "counters": {k: v for k, v in snap["counters"].items()
                     if k.startswith("decode.")},
    })

    # -- leg 3: the mp-sharded KV cache on the rule engine -------------------
    if jax.device_count() >= DEC_MP:
        ctxs = [mx.context.cpu(i) for i in range(DEC_MP)]
        axes = {"dp": 1, "mp": DEC_MP}
        mp_cell = _decode_cell(heads=DEC_MP)
        sharded = DecodeEngine(mp_cell, mp_cell.init_params(1),
                               slots=4, max_prompt_len=8,
                               max_new_tokens=8,
                               partition_rules=DECODE_PARTITION_RULES,
                               mesh_axes=axes, contexts=ctxs)
        mp_tokens = sharded.generate(prompt()).tokens
        sharded_bytes = sharded.stats()["kv_cache_bytes"]
        sharded.close()
        repl = DecodeEngine(mp_cell, mp_cell.init_params(1), slots=4,
                            max_prompt_len=8, max_new_tokens=8,
                            partition_rules=[], mesh_axes=axes,
                            contexts=ctxs, warmup=False)
        repl_bytes = repl.stats()["kv_cache_bytes"]
        repl.close()
        out["mp"] = {
            "mesh": axes,
            "sharded_kv_bytes": sharded_bytes,
            "replicated_kv_bytes": repl_bytes,
            "decoded_tokens": len(mp_tokens),
        }
    else:
        out["mp"] = None
    return emit(out, json_out)


if __name__ == "__main__":
    main({"--serve-smoke": serve_smoke, "--warm-smoke": warm_smoke,
          "--warm-child": lambda json_out: warm_child(),
          "--chaos-smoke": chaos_smoke,
          "--postmortem-smoke": postmortem_smoke,
          "--decode-smoke": decode_smoke},
         children=("--warm-child",))
