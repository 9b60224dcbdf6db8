"""Benchmark: ResNet-50 ImageNet-shape training throughput (img/s) + MFU.

Baseline of record (BASELINE.md): the reference's published 109 img/s for
ResNet-50 batch-32 training on 1x K80 (example/image-classification/
README.md:147-155). This harness runs the same workload shape — forward
+ backward + SGD-momentum update, batch images at 224x224 — as ONE jitted
XLA program on the local accelerator, with the TPU-native configuration:
channels-last (NHWC) layout end to end (which also triggers the
space-to-depth stem rewrite, ops/nn.py:_conv_s2d_7x7s2), bf16-resident
weights with fp32 master copies in the optimizer (the reference's
mp_sgd_update scheme, optimizer_op.cc:39-299), synthetic on-device data
(compute-bound measurement, matching the reference's benchmark_score.py
methodology).

See PERF.md for the measured roofline analysis of the MFU number.

Layout (the supervisor imports no JAX, so it never holds the chip: one
child at a time does):
  - a cheap probe child reports the device before the expensive raw
    child is launched; a run on a machine with no chip ends there, with
    a diagnostic and a non-zero exit (outside MXTPU_BENCH_SMOKE no phase
    falls back to the CPU);
  - the global deadline defaults to 1500s and every phase budget is
    clipped to the time remaining;
  - the raw measurement runs in its own child; on TimeoutExpired the
    supervisor salvages whatever JSON the child already printed from
    TimeoutExpired.stdout;
  - the optional Module.fit phase runs in a SEPARATE child with its own
    budget, so it can hang or die without touching the raw number;
  - the harness ALWAYS prints a final JSON line — the measurement on
    success, an {"error": ...} diagnostic otherwise; a round where no
    probe found a chip is marked {"skipped": true} so it reads as
    unmeasurable, not as a zero. The exit code is non-zero for either,
    and for a requested optional phase that yielded nothing
    ("failed_phases" names them);
  - every result line names the device it ran on (platform, kind,
    count), and the children keep JAX's persistent compile cache where
    mxnet_tpu.jax_cache.place() says (JAX_COMPILATION_CACHE_DIR, else
    <checkout>/.jax_cache);
  - partial results are emitted as they land ({..., "partial": true}
    lines), so an outer kill mid-phase salvages everything already
    measured;
  - phase deadlines are CLI-tunable: --budget-s 1200 rescales the total,
    --budget-s probe=60,raw=600,module=300 pins individual phases.

Prints one JSON line:
  {"metric", "value", "unit", "vs_baseline", "mfu", "device", ...}
"""
import json
import os
import subprocess
import sys
import time

BASELINE_IMG_S = 109.0  # reference ResNet-50 1xK80 (BASELINE.md)
SMOKE = os.environ.get("MXTPU_BENCH_SMOKE", "") == "1"
BATCH = 8 if SMOKE else int(os.environ.get("MXTPU_BENCH_BATCH", "128"))
IMG = 64 if SMOKE else 224
ITERS = 2 if SMOKE else 20
LR = 0.05
MOMENTUM = 0.9
# bf16-resident weights + fp32 master in the optimizer (mp_sgd scheme)
BF16 = True

# Per-phase budgets (seconds). The raw child gets the lion's share; the
# module phase is optional and must never eat the raw number's budget.
# TOTAL_DEADLINE bounds the whole harness and every phase budget is
# clipped to the time remaining.
PROBE_TIMEOUT = 75
PROBE_GAP = 20
RAW_TIMEOUT = 900
RAW_MIN = 240          # don't bother launching a raw child with less
MODULE_TIMEOUT = 540   # covers the fused AND phase-split fit measurements
DP_TIMEOUT = 900       # the optional data-parallel fused-vs-kvstore A/B:
                       # up to 2 legs PER axis size (vs module's 2 total),
                       # so it gets the raw-child-scale budget; a kill
                       # mid-sweep truncates to the sizes already banked
                       # (stdout partials AND the artifact update per size)
SERVE_TIMEOUT = 420    # the optional serving sweep (bucketed engine vs
                       # sequential Predictor + open-loop offered-load
                       # ladder); partial emission per load point
DECODE_TIMEOUT = 420   # the optional autoregressive-decode sweep
                       # (continuous-batching slot engine vs static
                       # whole-batch waves); partial emission per leg
TOTAL_DEADLINE = float(os.environ.get("MXTPU_BENCH_DEADLINE", "1500"))
# consecutive failed/timed-out probes before the supervisor stops
# probing and emits the diagnostic (a machine with no chip answers the
# same way every time)
PROBE_FAIL_LIMIT = int(os.environ.get("MXTPU_BENCH_PROBE_FAILS", "3"))


def _apply_budget_args(argv):
    """``--budget-s S`` / ``--budget-s probe=60,raw=600,module=300``:
    per-phase deadlines from the command line (whoever runs the harness
    under an outer time limit hands that window in; a bare number bounds
    the whole schedule, since every phase budget is clipped to the time
    remaining under it). Returns argv with the
    budget flags stripped; unknown phase names fail loudly."""
    global TOTAL_DEADLINE, PROBE_TIMEOUT, RAW_TIMEOUT, MODULE_TIMEOUT
    global DP_TIMEOUT, SERVE_TIMEOUT
    vals, rest, i = [], [], 0
    while i < len(argv):
        a = argv[i]
        if a == "--budget-s":
            i += 1
            if i >= len(argv):
                raise SystemExit("--budget-s: missing value "
                                 "(seconds, or probe=S,raw=S,...)")
            vals.append(argv[i])
        elif a.startswith("--budget-s="):
            vals.append(a.split("=", 1)[1])
        else:
            rest.append(a)
        i += 1
    names = {"probe": "PROBE_TIMEOUT", "raw": "RAW_TIMEOUT",
             "module": "MODULE_TIMEOUT", "dp": "DP_TIMEOUT",
             "serve": "SERVE_TIMEOUT", "total": "TOTAL_DEADLINE"}
    for v in vals:
        for part in v.split(","):
            if "=" in part:
                k, s = part.split("=", 1)
                if k not in names:
                    raise SystemExit("--budget-s: unknown phase %r "
                                     "(probe|raw|module|dp|serve|total)" % k)
            else:
                k, s = "total", part
            try:
                globals()[names[k]] = float(s)
            except ValueError:
                raise SystemExit("--budget-s: bad seconds value %r" % s)
    return rest

# Peak dense bf16 FLOP/s per chip by device kind (public spec sheets;
# v5e: Google Cloud documentation "TPU v5e", 197 TFLOP/s). A device that
# is not in the table is an error, not a default.
PEAK_FLOPS = [
    ("v6", 918e12), ("trillium", 918e12),
    ("v5p", 459e12), ("v5 lite", 197e12), ("v5e", 197e12), ("v5litepod", 197e12),
    ("v4", 275e12), ("v3", 123e12), ("v2", 46e12),
]

# Analytic ResNet-50 forward cost at 224x224, counting one MAC as 2 FLOPs
# (the convention every published MFU number uses): ~4.1 GFLOP/image.
# Backward is ~2x forward (grad wrt activations + grad wrt weights), so a
# train step is ~3x forward. The XLA cost model counts ~1.8x this
# (rematerialised fusions and formatting ops are billed as FLOPs), so the
# output reports BOTH: "mfu" from the cost model and "mfu_analytic" from
# this number — the latter is the one comparable to external reports.
ANALYTIC_FWD_FLOPS_PER_IMG_224 = 4.1e9


def peak_flops_for(kind):
    k = kind.lower()
    for sub, val in PEAK_FLOPS:
        if sub in k:
            return val
    raise ValueError("bench: no peak FLOP/s known for device kind %r — add "
                     "it to PEAK_FLOPS with its source" % (kind,))


def _init_device(jax):
    """First touch of the backend, in the one process that will hold
    the chip. Outside MXTPU_BENCH_SMOKE a machine with no accelerator is
    an error: a measurement path never falls back to the CPU. Places
    JAX's persistent compile cache before the first compile."""
    if SMOKE:  # harness logic check: cpu platform only, no accel touch
        jax.config.update("jax_platforms", "cpu")
        return jax.devices()[0]
    dev = jax.devices()[0]
    if dev.platform == "cpu":
        raise RuntimeError("bench: no accelerator — jax.devices()[0] is %r. "
                           "MXTPU_BENCH_SMOKE=1 is the CPU harness check."
                           % (dev,))
    from mxnet_tpu import jax_cache
    jax_cache.place()
    return dev


def _device_fields(jax, dev):
    """What every result line says about where it ran."""
    return {"platform": dev.platform, "device": dev.device_kind,
            "device_count": len(jax.devices())}


def probe():
    """Disposable child: init the backend and report the device."""
    import jax
    dev = _init_device(jax)
    print(json.dumps(_device_fields(jax, dev)), flush=True)


def child():
    import numpy as np
    import jax
    import jax.numpy as jnp

    dev = _init_device(jax)
    print("bench: device =", dev.device_kind, file=sys.stderr, flush=True)

    # Pinning default_device to host keeps every eager op (deferred-shape
    # pass, param init) off the accelerator; the first accel touch is the
    # jitted train step itself.
    cpu = jax.local_devices(backend="cpu")[0]

    with jax.default_device(cpu):
        import mxnet_tpu as mx
        from mxnet_tpu.gluon.model_zoo import vision
        from mxnet_tpu.gluon.block import make_pure_fn

        # Channels-last end to end — the MXU-native image layout
        # (mxnet_tpu/layout.py; effect quantified in PERF.md).
        mx.layout.set_default_layout("NHWC")
        np.random.seed(0)
        # MXTPU_BENCH_NET picks the model-zoo family member (the driver
        # path always measures resnet50_v1, the baseline of record; the
        # reference also publishes 18/34/101/152 numbers — BASELINE.md)
        net_name = os.environ.get("MXTPU_BENCH_NET", "resnet50_v1")
        net = getattr(vision, net_name)()
        net.initialize(mx.initializer.Xavier())
        net(mx.nd.ones((1, 32, 32, 3)))  # complete deferred shapes (on CPU)
        fn, raw_params, param_names = make_pure_fn(net, train=True)
        host_params = [np.asarray(p) for p in raw_params]

    n_params = len(host_params)
    bf16 = jnp.bfloat16
    # BatchNorm scale/shift and moving stats stay fp32 in the COMPUTE list
    # too (the cudnn BN convention; bf16 moving-average increments would
    # underflow) — only conv/fc weights are bf16-resident.
    keep_fp32 = [any(t in n for t in ("gamma", "beta", "running_mean",
                                      "running_var"))
                 for n in param_names]

    # Multi-precision step, the reference's mp_sgd_update scheme
    # (optimizer_op.cc:39-299): the compute path reads RESIDENT bf16
    # weights; fp32 master copies are touched only by the optimizer
    # update, which also emits the next step's bf16 weights. BatchNorm
    # running stats write back through the fp32 master list.
    # pbf holds ONLY the bf16-resident entries (conv/fc weights); fp32-kept
    # params (BN) come straight from the master list — aliasing them into
    # pbf would donate the same buffer twice.
    lowp = [BF16 and not keep_fp32[i] for i in range(n_params)]
    lowp_pos = {i: j for j, i in enumerate(
        [i for i in range(n_params) if lowp[i]])}

    def train_step(master, mom, pbf, x, y, rng):
        full = [pbf[lowp_pos[i]] if lowp[i] else master[i]
                for i in range(n_params)]

        def loss_f(ps):
            (logits,), aux = fn(ps, [x], rng)
            logp = jax.nn.log_softmax(logits.astype(jnp.float32))
            loss = -jnp.mean(jnp.take_along_axis(logp, y[:, None], axis=1))
            return loss, aux

        (loss, aux), grads = jax.value_and_grad(loss_f, has_aux=True)(full)
        new_master, new_mom, new_pbf = [], [], []
        for i in range(n_params):
            if i in aux:  # BatchNorm running stats: direct writeback (fp32)
                a32 = aux[i].astype(jnp.float32)
                new_master.append(a32)
                new_mom.append(mom[i])
                if lowp[i]:
                    new_pbf.append(a32.astype(bf16))
                continue
            m = MOMENTUM * mom[i] - LR * grads[i].astype(jnp.float32)
            w = master[i] + m
            new_master.append(w)
            new_mom.append(m)
            if lowp[i]:
                new_pbf.append(w.astype(bf16))
        return new_master, new_mom, new_pbf, loss

    step = jax.jit(train_step, donate_argnums=(0, 1, 2))   # mxlint: disable=jit-site -- standalone bench step: AOT-compiled below and registered via card_from_compiled, the card contract the wrapper exists for

    x = jax.device_put(
        np.random.uniform(-1, 1, (BATCH, IMG, IMG, 3)).astype(np.float32), dev)
    if BF16:
        x = x.astype(bf16)
    y = jax.device_put(
        np.random.randint(0, 1000, BATCH).astype(np.int32), dev)
    with jax.default_device(dev):
        rng = jax.random.key(0)
    master = [jax.device_put(p, dev) for p in host_params]
    mom = [jax.device_put(np.zeros_like(p), dev) for p in host_params]
    pbf = [master[i].astype(bf16) for i in range(n_params) if lowp[i]]

    # AOT-compile once; the SAME executable provides the FLOP count (its
    # own cost model), runs the warmup, AND runs the timing loop — one
    # callable throughout, no reliance on jit-cache behaviour. The
    # executable's cost/memory analysis is captured as a PROGRAM CARD
    # through the executor's shared card builder and registered in
    # telemetry.programs(), so tools/mfu_capture.py reads the step's
    # FLOPs/bytes straight from the bench line instead of requiring an
    # xprof hlo_stats capture.
    step_flops = None
    step_bytes = None
    step_card = None
    run = step
    try:
        from mxnet_tpu.executor import card_from_compiled
        from mxnet_tpu import telemetry as _tel
        t_c0 = time.perf_counter()
        compiled = step.lower(master, mom, pbf, x, y, rng).compile()
        run = compiled
        step_card = card_from_compiled("bench_step", compiled,
                                       entry="bench_step")
        step_card["compile_ms"] = round((time.perf_counter() - t_c0) * 1e3, 1)
        _tel.record_program(step_card)
        step_flops = step_card["flops"] or None
        step_bytes = step_card["bytes_accessed"] or None
    except Exception as e:
        print("bench: AOT compile/cost_analysis unavailable, using jit:", e,
              file=sys.stderr)

    # warmup; the scalar fetch waits for the queue to drain
    for _ in range(3):
        master, mom, pbf, loss = run(master, mom, pbf, x, y, rng)
    float(loss)

    import contextlib
    trace_dir = os.environ.get("MXTPU_BENCH_TRACE", "")
    tracer = (jax.profiler.trace(trace_dir) if trace_dir  # mfu_capture lane
              else contextlib.nullcontext())
    with tracer:
        t0 = time.perf_counter()
        for _ in range(ITERS):
            master, mom, pbf, loss = run(master, mom, pbf, x, y, rng)
        float(loss)
        dt = time.perf_counter() - t0

    img_s = BATCH * ITERS / dt
    out = {
        "metric": "%s_train_throughput" % net_name.replace("_v1", ""),
        "value": round(img_s, 2),
        "unit": "img/s",
        "vs_baseline": round(img_s / BASELINE_IMG_S, 3),
        "batch": BATCH,
        "layout": "NHWC",
        "precision": "bf16+fp32-master" if BF16 else "fp32",
    }
    out.update(_device_fields(jax, dev))
    try:
        from mxnet_tpu import telemetry as _tel
        out["process"] = _tel.process_identity()
    except Exception:                       # telemetry must never cost a run
        pass
    # utilisation is a device metric: the CPU harness check writes none
    peak = None if SMOKE else peak_flops_for(dev.device_kind)
    if step_flops and peak:
        flops_s = step_flops * ITERS / dt
        out["tflops_per_s"] = round(flops_s / 1e12, 2)
        out["mfu"] = round(flops_s / peak, 4)
    # per-step cost/memory card figures (mfu_capture's no-xprof path
    # and the PERF.md "Memory & cost telemetry" table read these)
    if step_flops:
        out["step_flops"] = step_flops
    if step_bytes:
        out["step_bytes_accessed"] = step_bytes
    if step_card is not None:
        out["program_card"] = {
            k: step_card.get(k) for k in
            ("id", "kind", "flops", "bytes_accessed", "peak_bytes",
             "argument_bytes", "output_bytes", "temp_bytes",
             "generated_code_bytes", "compile_ms")}
    # Analytic-FLOP MFU (the externally comparable number — see the
    # ANALYTIC_FWD_FLOPS_PER_IMG_224 comment).
    analytic_step = (3.0 * ANALYTIC_FWD_FLOPS_PER_IMG_224
                     * (IMG / 224.0) ** 2 * BATCH)
    if peak:
        a_flops_s = analytic_step * ITERS / dt
        out["tflops_per_s_analytic"] = round(a_flops_s / 1e12, 2)
        out["mfu_analytic"] = round(a_flops_s / peak, 4)

    print(json.dumps(out), flush=True)


def _telemetry_summary():
    """Trimmed ``mx.telemetry.snapshot()`` for the BENCH/MULTICHIP
    artifacts: the full counter registry (dispatches by kind, jit
    compiles vs. hits, fused-fallback codes, transfer bytes, blocking
    syncs) plus the fit-phase span percentiles — the per-phase numbers
    the next perf PR starts from. ``_module_fit_throughput`` resets the
    registry at the top of its timed window, so this reads as one leg's
    accounting."""
    try:
        import mxnet_tpu as mx
        snap = mx.telemetry.snapshot()
    except Exception as e:                  # telemetry must never cost a run
        return {"error": str(e)}
    from mxnet_tpu import telemetry as _tel
    spans = {k: v for k, v in snap["spans"].items()
             if k in _tel.FIT_PHASE_SPANS or k in _tel.SERVE_SPANS}
    # keep the flag: a disabled-telemetry leg's all-zero counters must
    # read as "instrumentation off", not as a measured zero
    return {"enabled": snap["enabled"], "counters": snap["counters"],
            "spans": spans,
            # rank/host identity: every banked bench JSON names the
            # process that measured it (fleet artifacts share one dir)
            "process": snap["process"],
            # per-leg program cards + the online FLOP/s estimate: what a
            # step COSTS, next to what it MEASURED
            "programs": snap["programs"], "online": snap["online"]}


# the executor-path children sample the flight recorder at this
# interval so BENCH/MULTICHIP artifacts gain per-phase TIMELINES
# (counter deltas, queue depth, ledger bytes, MFU per tick) next to
# the endpoint snapshots
BENCH_SAMPLER_MS = 100.0


def _sampler_begin():
    """Start (or restart the window of) the flight-recorder sampler for
    one bench leg. Telemetry must never cost a run — failures degrade
    to 'no series in the artifact'."""
    try:
        from mxnet_tpu import flight
        flight.series_clear()
        flight.sampler_start(BENCH_SAMPLER_MS)
    except Exception as e:
        print("bench: flight sampler unavailable: %s" % e,
              file=sys.stderr)


def _series_window(n=240):
    """The sampler's banked time-series window for the current leg."""
    try:
        from mxnet_tpu import flight
        return flight.series_window(n)
    except Exception as e:
        return {"error": str(e)}


_ROBUSTNESS_PREFIXES = ("faults.", "serving.shed", "serving.retries",
                        "serving.breaker", "serving.deadline",
                        "serving.dispatch_failures", "checkpoint.",
                        "divergence.", "training.preempted")


def _robustness_counters():
    """Per-leg fault/shed/resume counters (ISSUE 7): the robustness
    trajectory banked NEXT to the throughput trajectory, so a BENCH
    round records whether its numbers were measured under injected
    faults / shedding / resumes (all zeros = a clean leg — still worth
    recording, it's the claim the chaos lane checks against)."""
    try:
        from mxnet_tpu import telemetry
        return {k: v for k, v in telemetry.counters().items()
                if k.startswith(_ROBUSTNESS_PREFIXES)}
    except Exception as e:                  # telemetry must never cost a run
        return {"error": str(e)}


def module_child():
    """Separate child for the OPTIONAL user-facing-path measurement:
    Module.fit through the whole-step fused program AND, budget
    permitting, the phase-split oracle with the knob pinned off — the
    PERF.md "Module.fit gap" A/B in one child. The fused number is
    printed the moment it exists (partial-result emission: a hang in the
    phase-split leg leaves the fused line salvageable); any hang/crash
    here is absorbed by the supervisor without touching the raw number."""
    import jax
    dev = _init_device(jax)
    old_pin = os.environ.get("MXNET_MODULE_FUSED_STEP")
    try:
        os.environ["MXNET_MODULE_FUSED_STEP"] = "1"
        _sampler_begin()
        img_s, fallback = _module_fit_throughput(dev)
        out = {"module_fit_img_s": round(img_s, 2)}
        out.update(_device_fields(jax, dev))
        if fallback is not None:
            # a silent fallback would record two phase-split numbers as
            # the A/B — mark the leg so the number reads as what it
            # measured
            out["module_fit_fused_fallback"] = fallback
        out["telemetry"] = _telemetry_summary()
        out["robustness"] = _robustness_counters()
        # the leg's per-tick timeline next to its endpoint snapshot
        out["series"] = _series_window()
        print(json.dumps(out), flush=True)
        os.environ["MXNET_MODULE_FUSED_STEP"] = "0"
        _sampler_begin()
        img_s, _ = _module_fit_throughput(dev)
        out["module_fit_phase_split_img_s"] = round(img_s, 2)
        out["telemetry_phase_split"] = _telemetry_summary()
        out["robustness_phase_split"] = _robustness_counters()
        out["series_phase_split"] = _series_window()
        print(json.dumps(out), flush=True)
    finally:
        _restore_pin(old_pin)


def _restore_pin(old):
    """Put MXNET_MODULE_FUSED_STEP back (the A/B children flip it; an
    in-process caller — the harness tests drive the children directly —
    must not inherit the last leg's pin)."""
    if old is None:
        os.environ.pop("MXNET_MODULE_FUSED_STEP", None)
    else:
        os.environ["MXNET_MODULE_FUSED_STEP"] = old


def _module_fit_throughput(dev, contexts=None, kvstore="local",
                           module_kwargs=None):
    """Throughput of the USER-FACING training path — Module.fit itself
    (symbolic ResNet-50, bf16 executor via the InferType pass, fp32
    master weights in the optimizer, metric updates included) — so
    framework overhead above the raw fused step is a measured number.

    ``contexts`` (default: one device) selects the data-parallel mesh:
    the per-chip batch stays ``BATCH`` and the GLOBAL batch scales with
    the axis size, so per-axis img/s reads as scaling efficiency.
    ``kvstore`` feeds straight into Module.fit — the dp A/B runs the
    fused-SPMD step (subsumed in-process kvstore) against the pinned-off
    kvstore phase-split path."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    import mxnet_tpu as mx
    from mxnet_tpu.io import DataDesc, DataBatch, DataIter

    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        "examples", "image-classification"))
    from symbols.resnet import get_symbol

    n_iters = ITERS
    img = IMG
    sym = get_symbol(num_classes=1000, num_layers=50,
                     image_shape="3,%d,%d" % (img, img))
    bf16 = np.dtype(jnp.bfloat16)
    if contexts is None:
        contexts = [mx.cpu() if SMOKE else mx.tpu()]
    batch = BATCH * len(contexts)

    class _DeviceBatchIter(DataIter):
        """Synthetic iterator handing out the SAME device-resident batch
        (benchmark_score methodology — measures compute+framework, not
        host->device feeding; tools/decode_bench.py covers the input
        pipeline)."""

        def __init__(self, n):
            super().__init__(batch)
            rs = np.random.RandomState(0)
            xb = jax.device_put(rs.uniform(
                -1, 1, (batch, 3, img, img)).astype(np.float32), dev)
            yb = jax.device_put(rs.randint(
                0, 1000, batch).astype(np.float32), dev)
            from mxnet_tpu.ndarray.ndarray import _wrap
            self._batch = DataBatch([_wrap(xb.astype(bf16))],
                                    [_wrap(yb)], pad=0)
            self.n = n
            self.i = 0

        @property
        def provide_data(self):
            return [DataDesc("data", (batch, 3, img, img), dtype=bf16)]

        @property
        def provide_label(self):
            return [DataDesc("softmax_label", (batch,))]

        def reset(self):
            self.i = 0

        def next(self):
            if self.i >= self.n:
                raise StopIteration
            self.i += 1
            return self._batch

    mod = mx.mod.Module(sym, context=contexts, **(module_kwargs or {}))
    opt_params = {"learning_rate": LR, "momentum": MOMENTUM,
                  "multi_precision": True}
    metric = mx.metric.Accuracy()
    warm = _DeviceBatchIter(3)
    # warmup epoch binds, initializes, and compiles the fused program
    mod.fit(warm, eval_metric=metric, num_epoch=1, kvstore=kvstore,
            initializer=mx.initializer.Xavier(),
            optimizer="sgd", optimizer_params=opt_params)
    # The fit loop is fully asynchronous (fused one-dispatch update,
    # device-accumulated metric), so batch-end marks measure DISPATCH
    # rate; the clock may only stop after the device queue drains. Time
    # from the first batch mark to a post-fit scalar fetch and count the
    # remaining batches (epoch-end work rides inside the window — over a
    # real epoch it amortises to noise; n_iters is set high enough that
    # it stays <5% here too).
    marks = []
    n = max(n_iters, 40)
    timed = _DeviceBatchIter(n)
    # clean telemetry window: the banked snapshot covers the TIMED epoch
    # only (bind/compile/warmup accounting would read as steady-state)
    mx.telemetry.reset()
    mod.fit(timed, eval_metric=metric, num_epoch=1, kvstore=kvstore,
            optimizer="sgd", optimizer_params=opt_params,
            batch_end_callback=lambda p: marks.append(time.perf_counter()))
    # drain the queue: fetch every trainable param so the clock covers
    # the queued optimizer steps regardless of argument ordering
    import jax.numpy as _jnp
    float(sum(_jnp.sum(mod._exec.arg_dict[name]._data)
              for name in mod._param_names))
    dt = time.perf_counter() - marks[0]
    return batch * (len(marks) - 1) / dt, mod._fused_fallback_reason


def dp_child():
    """Data-parallel A/B child: Module.fit through the fused-SPMD step
    (in-process kvstore subsumed into the ONE mesh program) vs the
    kvstore phase-split path, per dp-axis size, per-chip batch pinned at
    BATCH. Every axis size's numbers are printed the moment they exist
    (partial-result emission — a hang at a larger axis size salvages the
    smaller ones), and the final object is also banked into the
    MULTICHIP artifact dir so the scaling trajectory is recorded per
    round. In smoke mode the mesh is the virtual 8-device CPU host."""
    import jax
    if SMOKE:
        flags = os.environ.get("XLA_FLAGS", "")
        if "--xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8").strip()
    dev = _init_device(jax)
    import mxnet_tpu as mx
    n_dev = len([d for d in jax.devices() if d.platform == dev.platform])
    axes_env = os.environ.get("MXTPU_BENCH_DP_AXES", "")
    if axes_env:
        sizes = [int(s) for s in axes_env.split(",")]
        dropped = [k for k in sizes if k > n_dev]
        if dropped:
            # skip ONLY the oversized entries — later valid sizes in the
            # operator's list must still be measured
            print("bench: dp axis size(s) %s exceed %d devices, skipped"
                  % (dropped, n_dev), file=sys.stderr, flush=True)
        sizes = [k for k in sizes if k <= n_dev]
    else:
        sizes, k = [], 1
        while k <= n_dev:
            sizes.append(k)
            k *= 2
    mk_ctx = mx.cpu if SMOKE else mx.tpu
    out = {"lane": "dp_ab", "n_devices": n_dev, "per_chip_batch": BATCH,
           "dp": {}}
    out.update(_device_fields(jax, dev))
    old_pin = os.environ.get("MXNET_MODULE_FUSED_STEP")
    try:
        for k in sizes:
            contexts = [mk_ctx(i) for i in range(k)]
            # at k=1 _create_kvstore resolves 'device' to NO kvstore, so
            # the split leg is the plain phase-split baseline — mark it
            # so the table never reads as a kvstore measurement there
            entry = {"split_kvstore_active": k > 1}
            os.environ["MXNET_MODULE_FUSED_STEP"] = "1"
            _sampler_begin()
            img_s, fallback = _module_fit_throughput(dev, contexts=contexts,
                                                     kvstore="device")
            entry["fused_img_s"] = round(img_s, 2)
            entry["telemetry"] = _telemetry_summary()
            entry["series"] = _series_window()
            if fallback is not None:
                # a silently fallen-back leg must not read as a fused
                # number
                entry["fused_fallback"] = getattr(fallback, "code",
                                                  str(fallback))
            os.environ["MXNET_MODULE_FUSED_STEP"] = "0"
            img_s, _ = _module_fit_throughput(dev, contexts=contexts,
                                              kvstore="device")
            entry["kvstore_img_s"] = round(img_s, 2)
            out["dp"][str(k)] = entry
            print(json.dumps(dict(out, partial=True)), flush=True)
            # re-bank the artifact after EVERY axis size: a hang/kill at
            # a larger mesh (the failure mode this lane exists to catch)
            # must not lose the sizes already measured
            _write_dp_artifact(dict(out, ok=False, skipped=False,
                                    truncated=True))
    finally:
        _restore_pin(old_pin)
    print(json.dumps(out), flush=True)
    _write_dp_artifact(dict(out, ok=True, skipped=False))


def _mp_bench_rules(mp):
    """ResNet partition rules for the mp A/B: shard conv/FC weight
    output channels (and batch-norm scale/shift vectors) over ``mp``.
    Non-divisible shapes downgrade to replicate (warned + counted) —
    the point of the lane is the LAYOUT cost A/B, not rule surgery
    per architecture."""
    from jax.sharding import PartitionSpec as P
    from mxnet_tpu.parallel import PartitionRules
    return PartitionRules([
        (r"(conv\d*|fc\d*)_weight$", P("mp")),
        (r"weight$", P("mp")),
        (r"(gamma|beta|bias)$", P("mp")),
    ])


def _write_mp_artifact(obj):
    """MULTICHIP artifact for the per-layout A/B (same schema stance as
    the dp artifact: partial writes marked, final write ok=True)."""
    art_dir = os.environ.get("MXTPU_ARTIFACT_DIR", "/tmp/mxtpu_artifacts")
    try:
        os.makedirs(art_dir, exist_ok=True)
        with open(os.path.join(art_dir, "multichip_mp_ab.json"), "w") as f:
            f.write(json.dumps(obj) + "\n")
    except OSError as e:
        print("bench: mp artifact write failed: %s" % e, file=sys.stderr)


def mp_child():
    """Partition-layout A/B child (ISSUE 15): Module.fit through the
    fused SPMD step on the SAME devices and global batch under two
    LAYOUTS — params replicated (plain dp over all devices) vs
    rule-sharded over a dp x mp mesh — banking per-layout img/s,
    telemetry and the per-layout PROGRAM CARDS (the card's
    ``partition`` block names the layout, so the corpus rows stay
    attributable). In smoke mode the mesh is the virtual 8-device CPU
    host as 2x4; on a TPU slice the mp axis defaults to 4 (v5e-8 ->
    2x4) or 2 when fewer chips answer. Partial results print per
    layout, mirroring dp_child's salvage discipline."""
    import jax
    if SMOKE:
        flags = os.environ.get("XLA_FLAGS", "")
        if "--xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8").strip()
    dev = _init_device(jax)
    import mxnet_tpu as mx
    from mxnet_tpu import telemetry
    n_dev = len([d for d in jax.devices() if d.platform == dev.platform])
    if n_dev < 2:
        out = {"lane": "mp_ab", "skipped": True,
               "reason": "mp A/B needs >=2 devices, found %d" % n_dev}
        out.update(_device_fields(jax, dev))
        print(json.dumps(out), flush=True)
        _write_mp_artifact(dict(out, ok=False))
        return
    mp = int(os.environ.get("MXTPU_BENCH_MP", "4"))
    while mp > 1 and n_dev % mp:
        mp //= 2
    dp = n_dev // max(mp, 1)
    mk_ctx = mx.cpu if SMOKE else mx.tpu
    contexts = [mk_ctx(i) for i in range(n_dev)]
    layouts = {
        "replicated": None,
        "dp%dxmp%d" % (dp, mp): {
            "partition_rules": _mp_bench_rules(mp),
            "mesh_axes": {"dp": dp, "mp": mp},
        },
    }
    out = {"lane": "mp_ab", "n_devices": n_dev, "per_chip_batch": BATCH,
           "mesh_axes": {"dp": dp, "mp": mp}, "layouts": {}}
    out.update(_device_fields(jax, dev))
    old_pin = os.environ.get("MXNET_MODULE_FUSED_STEP")
    try:
        os.environ["MXNET_MODULE_FUSED_STEP"] = "1"
        for name, kw in layouts.items():
            _sampler_begin()
            img_s, fallback = _module_fit_throughput(
                dev, contexts=contexts, kvstore="device",
                module_kwargs=kw)
            entry = {"img_s": round(img_s, 2),
                     "telemetry": _telemetry_summary(),
                     "series": _series_window()}
            if fallback is not None:
                entry["fused_fallback"] = getattr(fallback, "code",
                                                  str(fallback))
            # the layout's train_step card: what this layout COSTS
            # (FLOPs/bytes/peak HBM) plus its partition stamp
            entry["program_cards"] = {
                k: {kk: c.get(kk) for kk in
                    ("kind", "flops", "bytes_accessed", "peak_bytes",
                     "compile_ms", "dispatches", "partition")}
                for k, c in telemetry.programs().items()
                if c.get("kind") == "train_step" and c.get("dispatches")}
            out["layouts"][name] = entry
            print(json.dumps(dict(out, partial=True)), flush=True)
            _write_mp_artifact(dict(out, ok=False, truncated=True))
    finally:
        _restore_pin(old_pin)
    names = list(out["layouts"])
    if len(names) == 2 and all(
            out["layouts"][n].get("img_s") for n in names):
        out["mp_vs_replicated"] = round(
            out["layouts"][names[1]]["img_s"]
            / out["layouts"][names[0]]["img_s"], 3)
    print(json.dumps(out), flush=True)
    _write_mp_artifact(dict(out, ok=True))


def serve_child():
    """Inference-serving sweep: the bucketed micro-batching engine
    (mxnet_tpu/serving.py) vs the one-request-at-a-time Predictor loop,
    then an OPEN-LOOP offered-load ladder — requests arrive on a fixed
    schedule regardless of completions (the serving regime where queue
    depth and latency percentiles mean something), at fractions of the
    measured burst capacity. Every phase's numbers print the moment
    they exist ({"partial": true} lines), so a kill mid-ladder salvages
    the points already measured; per-bucket program cards ride in the
    artifact so a round records what each bucket COSTS next to what it
    served. Smoke mode swaps ResNet-50 for a tiny MLP (harness-logic
    check on CPU)."""
    import numpy as np
    import jax
    dev = _init_device(jax)
    import mxnet_tpu as mx
    from mxnet_tpu import telemetry
    from mxnet_tpu.predictor import Predictor
    from mxnet_tpu.serving import InferenceEngine

    rng = np.random.RandomState(0)
    if SMOKE:
        d = 16
        data = mx.sym.Variable("data")
        net = mx.sym.FullyConnected(data, num_hidden=64, name="fc1")
        net = mx.sym.Activation(net, act_type="relu")
        net = mx.sym.FullyConnected(net, num_hidden=4, name="fc2")
        sym = mx.sym.SoftmaxOutput(net, name="softmax")
        row = (d,)
        n_req, max_batch = 256, 16
    else:
        sys.path.insert(0, os.path.join(
            os.path.dirname(os.path.abspath(__file__)),
            "examples", "image-classification"))
        from symbols.resnet import get_symbol
        sym = get_symbol(num_classes=1000, num_layers=50,
                         image_shape="3,%d,%d" % (IMG, IMG))
        row = (3, IMG, IMG)
        n_req, max_batch = 128, 32
    arg_shapes, _, aux_shapes = sym.infer_shape_partial(
        data=(1,) + row)
    params = {}
    for name, shape in zip(sym.list_arguments(), arg_shapes):
        if name in ("data", "softmax_label"):
            continue
        params["arg:" + name] = mx.nd.array(
            rng.normal(0, 0.05, shape).astype(np.float32))
    for name, shape in zip(sym.list_auxiliary_states(), aux_shapes):
        # BatchNorm moving stats: mean 0 / var 1 keeps activations sane
        fill = np.ones if name.endswith("moving_var") else np.zeros
        params["aux:" + name] = mx.nd.array(fill(shape, np.float32))

    out = {"lane": "serving", "n_requests": n_req, "max_batch": max_batch}
    out.update(_device_fields(jax, dev))
    reqs = [rng.uniform(-1, 1, (1,) + row).astype(np.float32)
            for _ in range(min(n_req, 64))]

    def req_at(i):
        return reqs[i % len(reqs)]

    # leg 1: the one-request-at-a-time facade (the pre-engine baseline)
    pred = Predictor(sym, params, {"data": (1,) + row})
    pred.forward(data=req_at(0))
    pred.get_output(0).asnumpy()          # compile outside the window
    n_un = min(n_req, 48)
    t0 = time.perf_counter()
    for i in range(n_un):
        pred.forward(data=req_at(i))
        pred.get_output(0).asnumpy()
    out["unbatched_req_s"] = round(n_un / (time.perf_counter() - t0), 2)
    print(json.dumps(dict(out, partial=True)), flush=True)

    # leg 2: burst capacity through the bucketed engine (all buckets
    # AOT-compiled at construction — exactly one program per signature;
    # where a user set MXNET_COMPILE_CACHE and a prior round populated
    # it, construction deserializes instead of invoking XLA — the startup
    # wall and compile-cache counters bank the cold-vs-warm trajectory)
    _sampler_begin()      # per-tick timeline across burst + ladder
    t_eng = time.perf_counter()
    engine = InferenceEngine(sym, params, {"data": (1,) + row},
                             max_batch=max_batch, max_wait_ms=2.0,
                             max_inflight=4)
    out["engine_startup_s"] = round(time.perf_counter() - t_eng, 3)
    out["compile_cache"] = {
        k: v for k, v in telemetry.counters().items()
        if k.startswith("compile_cache.")}
    cards = engine.program_cards()
    out["buckets"] = engine.buckets
    out["program_cards"] = {
        k: {kk: c.get(kk) for kk in
            ("kind", "flops", "bytes_accessed", "peak_bytes",
             "compile_ms", "dispatches")}
        for k, c in cards.items()}
    out["compiles_per_bucket"] = round(
        len(cards) / len(engine.buckets), 2)
    telemetry.reset()
    t0 = time.perf_counter()
    futs = [engine.submit(data=req_at(i)) for i in range(n_req)]
    for f in futs:
        f.result(timeout=600)
    burst = n_req / (time.perf_counter() - t0)
    out["burst_req_s"] = round(burst, 2)
    out["serve_speedup"] = round(burst / out["unbatched_req_s"], 2) \
        if out["unbatched_req_s"] else None
    lat = telemetry.span_stats("serve_request").get("serve_request", {})
    out["burst_latency_ms"] = {k: lat.get(k)
                               for k in ("p50_ms", "p95_ms", "p99_ms")}
    print(json.dumps(dict(out, partial=True)), flush=True)

    # leg 3: open-loop ladder at fractions of burst capacity — arrivals
    # on a fixed schedule; latency is measured from the SCHEDULED
    # arrival (coordinated-omission-free)
    out["offered_loads"] = {}
    for frac in (0.5, 0.8, 0.95):
        rate = burst * frac
        telemetry.reset()
        lats, t0 = [], time.perf_counter()
        pend = []
        for i in range(n_req):
            sched = t0 + i / rate
            now = time.perf_counter()
            if sched > now:
                time.sleep(sched - now)
            fut = engine.submit(data=req_at(i))
            # stamp at RESOLUTION (the done callback runs on the
            # resolver thread at set_result) — collecting in submission
            # order would charge an early-resolved request for every
            # slower future ahead of it. list.append is GIL-atomic.
            fut.add_done_callback(
                lambda f, s=sched: lats.append(
                    (time.perf_counter() - s) * 1e3))
            pend.append(fut)
        for fut in pend:
            fut.result(timeout=600)
        dt = time.perf_counter() - t0
        lats.sort()
        # per-load fill from THIS window's counters (engine.stats() is
        # cumulative since construction)
        c = telemetry.counters()
        rows = c.get("serving.batch_rows", 0)
        pad = c.get("serving.pad_rows", 0)
        pct = telemetry._percentile      # the ONE percentile rule
        out["offered_loads"]["%.2f" % frac] = {
            "offered_req_s": round(rate, 2),
            "achieved_req_s": round(n_req / dt, 2),
            "latency_ms": {
                "p50": round(pct(lats, 50), 3),
                "p95": round(pct(lats, 95), 3),
                "p99": round(pct(lats, 99), 3),
            },
            "batch_fill": round(rows / (rows + pad), 4)
            if rows + pad else None,
            "batches": c.get("serving.batches", 0),
        }
        print(json.dumps(dict(out, partial=True)), flush=True)
    out["telemetry"] = _telemetry_summary()
    # the per-tick timeline across burst + offered-load ladder: the
    # perf trajectory gains per-phase timelines, not just endpoints
    out["series"] = _series_window()
    # the robustness trajectory: overload-control + fault counters for
    # this leg, plus the engine's own shed/retry/breaker accounting
    st = engine.stats()
    out["robustness"] = {
        "counters": _robustness_counters(),
        "engine": {k: st.get(k) for k in
                   ("shed_requests", "shed_rows", "shed_by_cause",
                    "retries", "dispatch_failures", "breaker",
                    "queued_rows", "max_queue_rows", "deadline_ms")},
    }
    engine.close()        # appends the corpus record when one is configured
    # the corpus-fed autotuner's plan for this round's traffic — what
    # the NEXT round's engine would pick instead of pow-2 buckets
    try:
        from mxnet_tpu import compile_cache
        from mxnet_tpu.tuner import plan_serving
        out["autotune_plan"] = plan_serving(
            compile_cache.corpus_records(kind="serving"),
            max_batch=max_batch)
    except Exception as e:
        print("bench: autotune plan unavailable: %s" % e, file=sys.stderr)
        out["autotune_plan"] = None
    print(json.dumps(out), flush=True)


def decode_child():
    """Continuous-batching decode sweep (mxnet_tpu/decode.py): the
    slot-pool engine streaming an open-loop skewed-length workload vs
    wave-synchronized static whole-batch decode of the same work
    through the same programs, plus per-token latency percentiles from
    the ``serve_decode_step`` spans (coordinated-omission-free: the
    spans time the dispatch cadence itself, with all work queued up
    front). Smoke mode shrinks the cell (harness-logic check on CPU);
    a real accelerator round banks the decode tokens/s trajectory
    PERF.md tracks."""
    import numpy as np
    import jax
    dev = _init_device(jax)
    from mxnet_tpu import telemetry
    from mxnet_tpu.decode import DecodeEngine, AttentionDecodeCell

    rng = np.random.RandomState(0)
    if SMOKE:
        cell = AttentionDecodeCell(vocab=256, embed=64, heads=8,
                                   head_dim=16, max_len=64)
        slots, waves, short, long_ = 8, 4, 4, 32
    else:
        cell = AttentionDecodeCell(vocab=8192, embed=512, heads=8,
                                   head_dim=64, max_len=512)
        slots, waves, short, long_ = 16, 4, 16, 192
    prompt_len = 4 if SMOKE else 16

    out = {"lane": "decode", "slots": slots, "waves": waves,
           "gen_short": short, "gen_long": long_}
    out.update(_device_fields(jax, dev))

    def prompt():
        return rng.randint(1, cell.vocab - 1, prompt_len) \
            .astype(np.int32)

    _sampler_begin()
    t_eng = time.perf_counter()
    engine = DecodeEngine(cell, cell.init_params(1), slots=slots,
                          max_prompt_len=prompt_len * 2,
                          max_new_tokens=long_)
    out["engine_startup_s"] = round(time.perf_counter() - t_eng, 3)
    out["program_cards"] = {
        k: {kk: c.get(kk) for kk in
            ("kind", "flops", "peak_bytes", "compile_ms", "dispatches")}
        for k, c in engine.program_cards().items()}
    out["kv_cache_bytes"] = engine.stats()["kv_cache_bytes"]
    print(json.dumps(dict(out, partial=True)), flush=True)

    plan = [[(prompt(), long_ if s == 0 else short)
             for s in range(slots)] for _ in range(waves)]
    total_tokens = sum(n for wave in plan for _, n in wave)
    stream = sorted((seq for wave in plan for seq in wave),
                    key=lambda s: -s[1])

    # leg 1: static whole-batch (wave-synchronized — finished lanes
    # idle until the wave's longest member completes)
    telemetry.reset()
    t0 = time.perf_counter()
    for wave in plan:
        futs = [engine.submit(p, max_new_tokens=n) for p, n in wave]
        for f in futs:
            f.result(timeout=600)
    dt_static = time.perf_counter() - t0
    out["static_tok_s"] = round(total_tokens / dt_static, 1)
    print(json.dumps(dict(out, partial=True)), flush=True)

    # leg 2: continuous — same work, open-loop, per-step admission
    telemetry.reset()
    t0 = time.perf_counter()
    futs = [engine.submit(p, max_new_tokens=n) for p, n in stream]
    for f in futs:
        f.result(timeout=600)
    dt_cont = time.perf_counter() - t0
    snap = telemetry.snapshot()
    lat = snap["spans"].get("serve_decode_step", {})
    out.update({
        "total_tokens": total_tokens,
        "continuous_tok_s": round(total_tokens / dt_cont, 1),
        "decode_speedup": round(dt_static / dt_cont, 2),
        "token_latency_ms": {k: lat.get(k)
                             for k in ("p50_ms", "p95_ms", "p99_ms")},
        "jit_compiles_timed": snap["spans"].get(
            "jit_compile", {}).get("count", 0),
        "counters": {k: v for k, v in snap["counters"].items()
                     if k.startswith("decode.")},
    })
    out["series"] = _series_window()
    st = engine.stats()
    out["stats"] = {k: st.get(k) for k in
                    ("tokens", "steps", "slot_fill", "shed_requests",
                     "retries", "dispatch_failures")}
    engine.close()       # appends the decode corpus record when configured
    print(json.dumps(out), flush=True)


def _write_dp_artifact(obj):
    """MULTICHIP artifact schema superset: n_devices/ok/skipped plus the
    per-axis-size img/s table (ok=False+truncated=True until the sweep
    completes, so a killed run reads as partial, not as a clean round)."""
    art_dir = os.environ.get("MXTPU_ARTIFACT_DIR", "/tmp/mxtpu_artifacts")
    try:
        os.makedirs(art_dir, exist_ok=True)
        with open(os.path.join(art_dir, "multichip_dp_ab.json"), "w") as f:
            f.write(json.dumps(obj) + "\n")
    except OSError as e:
        print("bench: dp artifact write failed: %s" % e, file=sys.stderr)


def _last_json_line(text):
    """Salvage the last parseable JSON object line from child stdout.
    TimeoutExpired.stdout may be bytes even under text=True."""
    if isinstance(text, bytes):
        text = text.decode("utf-8", "replace")
    for line in reversed((text or "").splitlines()):
        line = line.strip()
        if line.startswith("{"):
            try:
                return json.loads(line)
            except ValueError:
                pass
    return None


def _run_phase(mode, timeout, env_extra=None):
    """Run one child phase; return (parsed_json_or_None, timed_out)."""
    env = None
    if env_extra:
        env = dict(os.environ)
        env.update(env_extra)
    try:
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__), mode],
            stdout=subprocess.PIPE, text=True, timeout=timeout, env=env)
    except subprocess.TimeoutExpired as e:
        # the child prints its JSON the moment it has it — salvage it
        return _last_json_line(e.stdout), True
    parsed = _last_json_line(proc.stdout)
    if proc.returncode != 0:
        print("bench: %s exited rc=%d" % (mode, proc.returncode),
              file=sys.stderr, flush=True)
    return parsed, False


def supervise():
    """Probe-gated supervision under one hard deadline.

    A probe child (~75s budget) reports the device; the expensive raw
    child is launched only after a probe succeeds. PROBE_FAIL_LIMIT
    consecutive failed probes end the round with the diagnostic instead
    of spending the whole deadline on a machine that has no chip. A raw
    child that then fails sends us back to probing. Whatever happens,
    exactly one final JSON line is printed — the measurement, or an
    {"error": ...} diagnostic — and the cold-start seconds of every
    probe attempt ride in it either way. The exit code is 0 only when
    the raw number AND every requested optional phase were measured.
    """
    t0 = time.monotonic()

    def remaining():
        return TOTAL_DEADLINE - (time.monotonic() - t0)

    def phase_budget(want):
        # strictly bounded by the global deadline (a floor above
        # remaining() would overrun it); 1s keeps subprocess.run valid
        return max(1.0, min(want, remaining()))

    if SMOKE:
        out, _ = _run_phase("--child", phase_budget(RAW_TIMEOUT))
        if out and "value" in out:
            print(json.dumps(out))
            return 0
        print(json.dumps({"error": "smoke child yielded no measurement"}))
        return 1

    out = None
    probes = fails = consec_probe_fails = 0
    probe_aborted = False
    probe_info = None
    probe_seconds = []       # cold-start wall per probe attempt
    while out is None and remaining() > PROBE_TIMEOUT:
        t_probe = time.monotonic()
        info, timed_out = _run_phase("--probe", phase_budget(PROBE_TIMEOUT))
        probes += 1
        probe_seconds.append(round(time.monotonic() - t_probe, 1))
        if not info:
            consec_probe_fails += 1
            print("bench: probe %d %s (%.0fs left)" %
                  (probes, "timed out" if timed_out else "failed",
                   remaining()), file=sys.stderr, flush=True)
            if consec_probe_fails >= PROBE_FAIL_LIMIT:
                # no chip here: every further probe would find the same
                # — emit the diagnostic NOW and hand the unspent budget
                # back to the caller
                probe_aborted = True
                print("bench: %d consecutive failed probes — no "
                      "accelerator for this round" % consec_probe_fails,
                      file=sys.stderr, flush=True)
                break
            time.sleep(min(PROBE_GAP, max(0.0, remaining() - PROBE_TIMEOUT)))
            continue
        consec_probe_fails = 0
        probe_info = info
        print("bench: probe %d ok: %s" % (probes, json.dumps(info)),
              file=sys.stderr, flush=True)
        if remaining() < RAW_MIN:
            break  # too late to measure; the diagnostic reports the probe
        out, timed_out = _run_phase(
            "--child", phase_budget(RAW_TIMEOUT),
            env_extra={"MXNET_FUSED_BN_ADD_RELU": "0"})  # pinned baseline
        if out and "value" in out:
            if timed_out:
                out["salvaged"] = True
            break
        out = None
        fails += 1
        print("bench: raw attempt %d yielded no measurement (%.0fs left)"
              % (fails, remaining()), file=sys.stderr, flush=True)
        if fails >= 3:
            break

    if out is None:
        if probe_info is None:
            detail = "no probe child found an accelerator"
            if probe_aborted:
                detail += (" (%d consecutive failed probes; remaining "
                           "probes skipped)" % consec_probe_fails)
        elif fails:
            detail = "raw child failed after successful probe"
        else:
            detail = "deadline expired before a raw attempt could start"
        diag = {
            "error": "no measurement",
            # skipped=true marks a round with NO chip for the record
            # books: the number was never measurable, not measured-as-zero
            "skipped": probe_info is None,
            "probes": probes, "probe_ok": probe_info is not None,
            "probe_seconds": probe_seconds,
            "probe_aborted": probe_aborted,
            "raw_fails": fails, "deadline_s": TOTAL_DEADLINE,
            "detail": detail,
        }
        if probe_info:
            diag["probe_device"] = probe_info
        print(json.dumps(diag))
        return 1
    out["probe_seconds"] = probe_seconds

    # partial-result emission: the raw number is banked on stdout NOW —
    # if a later optional phase hangs past the caller's window, the kill
    # salvages this line instead of zeroing the round
    print(json.dumps(dict(out, partial=True)), flush=True)

    # The optional phases, each in its own child, each ON unless its env
    # switch says 0: (switch, mode, budget, least seconds worth starting
    # with, extra env, merge). merge() folds the child's JSON into `out`
    # and says whether the phase yielded its number; a requested phase
    # that did not is named in "failed_phases" and fails the run.
    def merge_module(r):
        if not (r and "module_fit_img_s" in r):
            return False
        out.update((k, v) for k, v in r.items() if k.startswith("module_fit"))
        return True

    def merge_dp(r):
        if not (r and r.get("dp")):
            return False
        out["dp"] = r["dp"]
        out["dp_per_chip_batch"] = r.get("per_chip_batch", BATCH)
        return True

    def merge_lane(lane):
        def merge(r):
            if not (r and r.get("lane") == lane):
                return False
            out[lane] = {k: v for k, v in r.items()
                         if k not in ("lane", "partial")}
            return True
        return merge

    def merge_ab(r):
        # end-to-end A/B of the fused BN-tail kernel (PERF.md: the whole
        # step, not the isolated pass, decides the knob)
        if not (r and "value" in r):
            return False
        out["img_s_fused_bn_tail"] = r["value"]
        return True

    phases = [
        ("MXTPU_BENCH_MODULE", "--module-child", MODULE_TIMEOUT, 180, None,
         merge_module),
        ("MXTPU_BENCH_DP", "--dp-child", DP_TIMEOUT, 180, None, merge_dp),
        ("MXTPU_BENCH_SERVE", "--serve-child", SERVE_TIMEOUT, 120, None,
         merge_lane("serving")),
        ("MXTPU_BENCH_DECODE", "--decode-child", DECODE_TIMEOUT, 120, None,
         merge_lane("decode")),
        ("MXTPU_BENCH_AB", "--child", RAW_TIMEOUT, RAW_MIN,
         {"MXNET_FUSED_BN_ADD_RELU": "1"}, merge_ab),
    ]
    failed = []
    for switch, mode, budget, least, env_extra, merge in phases:
        if os.environ.get(switch, "1") != "1":
            continue
        name = switch[len("MXTPU_BENCH_"):].lower()
        if remaining() <= least:
            print("bench: %s phase not started: %.0fs left" %
                  (name, remaining()), file=sys.stderr, flush=True)
            failed.append(name)
            continue
        res, timed_out = _run_phase(mode, phase_budget(budget),
                                    env_extra=env_extra)
        if not merge(res):
            print("bench: %s phase yielded no number (raw result kept)"
                  % name, file=sys.stderr, flush=True)
            failed.append(name)
            continue
        if name == "ab" and timed_out:
            out["fused_bn_tail_salvaged"] = True
        print(json.dumps(dict(out, partial=True)), flush=True)

    if failed:
        out["failed_phases"] = failed
    print(json.dumps(out))
    return 1 if failed else 0


if __name__ == "__main__":
    _argv = _apply_budget_args(sys.argv[1:])
    if "--child" in _argv:
        child()
    elif "--probe" in _argv:
        probe()
    elif "--module-child" in _argv:
        module_child()
    elif "--dp-child" in _argv:
        dp_child()
    elif "--mp-child" in _argv:
        mp_child()
    elif "--serve-child" in _argv:
        serve_child()
    elif "--decode-child" in _argv:
        decode_child()
    else:
        sys.exit(supervise())
