#!/usr/bin/env python3
"""Break down where Module.fit's wall-clock goes vs the raw fused step
(PERF.md: the round-5 bench measured 157.9 img/s user-path vs 2254 raw).

Times each fit-loop phase IN ISOLATION on the attached accelerator:
  - forward_backward (the fused executor program)
  - update           (FusedUpdater one-dispatch step)
  - update_metric    (device-accumulated Accuracy)
  - epoch-end get_params/set_params round trip

Run on a TPU host:  python tools/module_fit_probe.py
Smoke (CPU):        MXTPU_PROBE_SMOKE=1 python tools/module_fit_probe.py
Fit-smoke lane:     python tools/module_fit_probe.py --fit-smoke \
                        [--json-out PATH]
  (tier-1 CI: tiny-MLP Module.fit on the CPU backend, 20 batches, fused
  vs phase-split A/B with per-batch dispatch counts — the user-path
  trajectory is captured every round, chip or no chip)
DP-smoke lane:      python tools/module_fit_probe.py --dp-smoke \
                        [--json-out PATH]
  (tier-1 CI: tiny-MLP Module.fit on the virtual 8-device CPU mesh —
  the fused-SPMD data-parallel step vs the kvstore phase-split path;
  asserts dp-fused >= phase-split img/s and EXACTLY 1 jitted-program
  dispatch per batch via the mx.telemetry dispatch registry)
MP-smoke lane:      python tools/module_fit_probe.py --mp-smoke \
                        [--json-out PATH]
  (tier-1 CI: the same MLP on the 8-device CPU mesh laid out as a 2x4
  dp x mp mesh with every parameter rule-sharded over mp
  (parallel.partition.PartitionRules): gates 1 fused dispatch/batch,
  zero fused fallbacks, per-device committed param bytes ~ 1/mp of
  the replicated layout per the buffer ledger, and fused >=
  phase-split img/s)
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

SMOKE = os.environ.get("MXTPU_PROBE_SMOKE", "") == "1"
FIT_SMOKE = "--fit-smoke" in sys.argv
DP_SMOKE = "--dp-smoke" in sys.argv
MP_SMOKE = "--mp-smoke" in sys.argv
DIST_SMOKE = "--dist-smoke" in sys.argv
DIST_CHILD = "--dist-child" in sys.argv
# a dist child that dies on an injected fault exits THROUGH
# mx.dist.abort with this code (destructor-free death: a crashing
# worker must not drag survivors into the coordination shutdown
# barrier); the parent gates on it
DIST_FAULT_RC = 21
N_DEV = 8
BATCH = 8 if SMOKE else 128
IMG = 32 if SMOKE else 224
ITERS = 2 if SMOKE else 10

if DP_SMOKE or MP_SMOKE:
    # the virtual mesh flag must land before the CPU backend initialises
    _flags = os.environ.get("XLA_FLAGS", "")
    if "--xla_force_host_platform_device_count" not in _flags:
        os.environ["XLA_FLAGS"] = (
            _flags + " --xla_force_host_platform_device_count=%d" % N_DEV
        ).strip()

import numpy as np
import jax
import jax.numpy as jnp

if SMOKE or FIT_SMOKE or DP_SMOKE or MP_SMOKE or DIST_SMOKE or DIST_CHILD:
    jax.config.update("jax_platforms", "cpu")

import mxnet_tpu as mx
from mxnet_tpu.io import DataDesc

sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "..",
    "examples", "image-classification"))
from symbols.resnet import get_symbol


def timed(label, fn, fence, iters=ITERS):
    """``fence`` must return (or contain) buffers DATA-DEPENDENT on the
    work ``fn`` queued — a fresh unrelated transfer does NOT drain the
    compute queue, so fencing on one under-reports any async phase."""
    fn()  # warm
    np.asarray(jax.tree_util.tree_leaves(
        jax.block_until_ready(fence()))[0])
    t0 = time.perf_counter()
    for _ in range(iters):
        fn()
    np.asarray(jax.tree_util.tree_leaves(
        jax.block_until_ready(fence()))[0])
    dt = (time.perf_counter() - t0) / iters
    print("%-28s %8.2f ms" % (label, dt * 1e3), flush=True)
    return dt


def main():
    dev = jax.devices()[0]
    print("device:", dev.device_kind, flush=True)
    sym = get_symbol(num_classes=1000, num_layers=50,
                     image_shape="3,%d,%d" % (IMG, IMG))
    bf16 = np.dtype(jnp.bfloat16)
    mod = mx.mod.Module(sym, context=mx.tpu() if dev.platform != "cpu"
                        else mx.cpu())
    mod.bind(data_shapes=[DataDesc("data", (BATCH, 3, IMG, IMG),
                                   dtype=bf16)],
             label_shapes=[DataDesc("softmax_label", (BATCH,))],
             for_training=True)
    mod.init_params(initializer=mx.initializer.Xavier())
    mod.init_optimizer(optimizer="sgd",
                       optimizer_params={"learning_rate": 0.05,
                                         "momentum": 0.9,
                                         "multi_precision": True})
    rs = np.random.RandomState(0)
    x = mx.nd.array(rs.uniform(-1, 1, (BATCH, 3, IMG, IMG))
                    .astype(np.float32)).astype(bf16)
    y = mx.nd.array(rs.randint(0, 1000, BATCH).astype(np.float32))
    from mxnet_tpu.io import DataBatch
    batch = DataBatch([x], [y], pad=0)
    metric = mx.metric.Accuracy()

    def grad_fence():
        return [g._data for g in mod._exec.grad_arrays if g is not None]

    def param_fence():
        return [mod._exec.arg_dict[n]._data for n in mod._param_names[:1]]

    def metric_fence():
        return metric._dev_sum

    results = {}
    results["forward_backward_ms"] = timed(
        "forward_backward", lambda: mod.forward_backward(batch),
        grad_fence) * 1e3
    results["update_ms"] = timed("update", lambda: mod.update(),
                                 param_fence) * 1e3
    results["update_metric_ms"] = timed(
        "update_metric",
        lambda: mod.update_metric(metric, batch.label), metric_fence) * 1e3

    def whole_step():
        mod.forward_backward(batch)
        mod.update()
        mod.update_metric(metric, batch.label)

    step_s = timed("whole step (fb+upd+metric)", whole_step,
                   lambda: (param_fence(), metric_fence()))
    results["step_ms"] = step_s * 1e3
    results["step_img_s"] = BATCH / step_s

    def epoch_end():
        arg_p, aux_p = mod.get_params()
        mod.set_params(arg_p, aux_p)

    results["epoch_end_get_set_ms"] = timed(
        "epoch-end get/set_params", epoch_end, param_fence,
        iters=max(2, ITERS // 3)) * 1e3

    print(json.dumps({k: round(v, 2) for k, v in results.items()}),
          flush=True)


def _smoke_lane(lane, contexts, kvstore, rounds, nbatch, batch,
                speed_key, extra=None, json_out=None, module_kwargs=None):
    """The ONE tier-1 lane harness both smoke lanes share: tiny-MLP
    ``Module.fit``, fused whole-step program vs phase-split oracle, with
    jitted-program dispatch counts per batch AND per-phase host-span
    timings read from the TELEMETRY registry (``mx.telemetry`` — the
    probe used to install its own single-slot ``executor.dispatch_hook``
    and duplicate the accounting; the multi-subscriber registry owns it
    now), and interleaved best-of timing (one epoch is a ~10ms window
    and share-throttled CI boxes drift in sustained speed — timing the
    two paths back to back inside each round keeps the RATIO honest
    under drift, and the min converges on the dispatch floor under spike
    noise). One JSON object on stdout (and to ``json_out``) — the
    artifact the CI lane banks each round. Returns (out, dispatch)."""
    import mxnet_tpu as mx
    from mxnet_tpu import telemetry
    from mxnet_tpu.io import DataIter, DataDesc, DataBatch

    d, c = 16, 4
    rs = np.random.RandomState(0)

    class _PreslicedIter(DataIter):
        """Device-resident pre-sliced batches (bench/benchmark_score
        methodology): the lane measures framework DISPATCH overhead —
        the thing the fused step removes — not numpy slicing; the input
        pipeline has its own probes (tools/decode_bench.py)."""

        def __init__(self):
            super().__init__(batch)
            self._batches = [DataBatch(
                [mx.nd.array(rs.uniform(-1, 1, (batch, d))
                             .astype(np.float32))],
                [mx.nd.array(rs.randint(0, c, batch)
                             .astype(np.float32))], pad=0)
                for _ in range(nbatch)]
            self.i = 0

        @property
        def provide_data(self):
            return [DataDesc("data", (batch, d))]

        @property
        def provide_label(self):
            return [DataDesc("softmax_label", (batch,))]

        def reset(self):
            self.i = 0

        def next(self):
            if self.i >= len(self._batches):
                raise StopIteration
            self.i += 1
            return self._batches[self.i - 1]

    def mlp():
        data = mx.sym.Variable("data")
        net = mx.sym.FullyConnected(data, num_hidden=64, name="fc1")
        net = mx.sym.Activation(net, act_type="relu")
        net = mx.sym.FullyConnected(net, num_hidden=c, name="fc2")
        return mx.sym.SoftmaxOutput(net, name="softmax")

    opt_params = {"learning_rate": 0.05, "momentum": 0.9}

    def setup(fused):
        os.environ["MXNET_MODULE_FUSED_STEP"] = "1" if fused else "0"
        mod = mx.mod.Module(mlp(), context=contexts,
                            **(module_kwargs or {}))
        metric = mx.metric.Accuracy()
        train = _PreslicedIter()
        # warm epoch: bind + init + compile land outside the timed window
        mod.fit(train, eval_metric=metric, num_epoch=1, kvstore=kvstore,
                initializer=mx.initializer.Xavier(),
                optimizer="sgd", optimizer_params=opt_params)
        reason = mod._fused_fallback_reason
        if fused and reason is not None:
            raise SystemExit("%s: fused path fell back: %s (%s)"
                             % (lane, reason, getattr(reason, "code", "?")))
        if not fused and getattr(reason, "code", None) != "env_pin":
            raise SystemExit("%s: phase-split leg expected the env_pin "
                             "fallback code, got %r" % (lane, reason))
        return mod, metric, train

    def epoch(state, fused):
        mod, metric, train = state
        os.environ["MXNET_MODULE_FUSED_STEP"] = "1" if fused else "0"
        # clean registry window: the counters/spans read after this
        # epoch describe THIS epoch alone
        telemetry.reset()
        t0 = time.perf_counter()
        mod.fit(train, eval_metric=metric, num_epoch=1, kvstore=kvstore,
                optimizer="sgd", optimizer_params=opt_params)
        # the loop is async — close the window on a data-dependent fetch
        metric.get()
        float(np.asarray(
            mod._exec.arg_dict[mod._param_names[0]]._data).sum())
        return time.perf_counter() - t0

    states = {True: setup(True), False: setup(False)}
    dts = {True: float("inf"), False: float("inf")}
    dispatch = {True: {}, False: {}}
    phases = {True: {}, False: {}}
    cards = {True: {}, False: {}}
    # the lane's accounting READS the registry, so recording must be on
    # for its window regardless of the ambient MXNET_TELEMETRY pin
    # (restored after — the lane must not flip the session's state)
    was_enabled = telemetry.enabled()
    telemetry.enable()
    try:
        for _ in range(rounds):
            for f in (True, False):
                dt = epoch(states[f], f)
                if dt <= dts[f]:
                    # bank the registry window of the BEST round, so
                    # the per-phase timings in the artifact describe
                    # the same epoch as the best-of img/s next to them
                    dts[f] = dt
                    dispatch[f] = telemetry.dispatch_counts()
                    phases[f] = {
                        name: {"count": s["count"],
                               "total_ms": s["total_ms"],
                               "p50_ms": s["p50_ms"],
                               "p95_ms": s["p95_ms"]}
                        for name, s in telemetry.span_stats().items()
                        if name in telemetry.FIT_PHASE_SPANS}
                    # program cards dispatched in the banked window:
                    # what each leg's step COSTS (FLOPs / peak HBM)
                    # rides next to what it measured
                    cards[f] = {
                        k: {kk: c.get(kk) for kk in
                            ("kind", "flops", "bytes_accessed",
                             "peak_bytes", "compile_ms", "dispatches")}
                        for k, c in telemetry.programs().items()
                        if c.get("dispatches")}
    finally:
        if not was_enabled:
            telemetry.disable()

    def report(f):
        return {
            "img_s": round(batch * nbatch / dts[f], 1),
            "dispatches_per_batch": round(
                sum(dispatch[f].values()) / nbatch, 2),
            "dispatch_counts": dispatch[f],
            "phase_spans": phases[f],
            "program_cards": cards[f],
        }

    fused, split = report(True), report(False)
    out = {"lane": lane, "platform": jax.devices()[0].platform}
    out.update(extra or {})
    out.update({
        "batch": batch, "nbatch": nbatch,
        "fused": fused, "phase_split": split,
        speed_key: round(fused["img_s"] / split["img_s"], 2),
    })
    line = json.dumps(out)
    print(line, flush=True)
    if json_out:
        with open(json_out, "w") as f:
            f.write(line + "\n")
    return out, dispatch


# the fit-smoke gate floor/ceiling: the recalibrated expectation is
# clamped into [FIT_GATE_FLOOR, FIT_GATE_CAP] — the lane always demands
# SOME fused win, and never demands more than the old absolute 3x
FIT_GATE_FLOOR = 1.2
FIT_GATE_CAP = 3.0
FIT_GATE_MARGIN = 0.7    # pass at 70% of the span-predicted speedup


def _recalibrated_fit_gate(out):
    """The fit-smoke speedup gate, recalibrated IN-RUN from the banked
    phase spans instead of an absolute ratio. The absolute >=3x gate
    false-fails on share-throttled boxes (2.4x at seed there): when the
    box inflates the non-dispatch overhead (python loop, callbacks,
    iterator) that BOTH legs pay, the achievable ratio shrinks even
    though the fused path still removes the whole dispatch chain. So
    predict the achievable wall from the split leg's own accounting —
    fused_wall ~= split_wall - split_dispatch_spans + fused_dispatch
    spans (the fused step replaces the split chain, everything else
    stays) — and gate at FIT_GATE_MARGIN of that prediction, clamped to
    [FIT_GATE_FLOOR, FIT_GATE_CAP]. On a healthy box the prediction is
    ~3-4x so the gate stays ~3x-strength; on a throttled box it relaxes
    to what the box can actually show. Dispatch-count gates stay
    absolute — they are noise-free."""
    # leaf phases only: fit_batch NESTS feed/step/... and would double
    # count; io_next is iterator time both legs pay identically
    leaf = ("feed", "step", "opt_update", "metric_update",
            "metric_fetch", "kv_push", "kv_pull")

    def disp_ms(leg):
        return sum(s.get("total_ms", 0.0)
                   for name, s in out[leg]["phase_spans"].items()
                   if name in leaf)

    wall_ms = {leg: out["batch"] * out["nbatch"] / out[leg]["img_s"] * 1e3
               for leg in ("fused", "phase_split")}
    predicted_fused = max(wall_ms["phase_split"] - disp_ms("phase_split")
                          + disp_ms("fused"), 1e-6)
    expected = max(wall_ms["phase_split"] / predicted_fused, 1.0)
    gate = min(FIT_GATE_CAP, max(FIT_GATE_FLOOR,
                                 FIT_GATE_MARGIN * expected))
    return round(expected, 2), round(gate, 2)


def fit_smoke(json_out=None, nbatch=20, batch=32):
    """Tier-1 smoke lane: tiny-MLP ``Module.fit`` on the CPU backend,
    fused whole-step program vs phase-split oracle (best-of-9
    interleaved), gated against the in-run recalibrated speedup
    expectation (see ``_recalibrated_fit_gate``)."""
    import mxnet_tpu as mx
    out, dispatch = _smoke_lane(
        "module_fit_smoke", mx.cpu(), "local", rounds=9,
        nbatch=nbatch, batch=batch, speed_key="fit_speedup",
        json_out=None)
    expected, gate = _recalibrated_fit_gate(out)
    out["fit_speedup_expected"] = expected
    out["fit_gate"] = gate
    # the fit acceptance gates: the deterministic dispatch counts plus
    # the recalibrated throughput ratio
    try:
        assert out["fused"]["dispatches_per_batch"] <= 2.0, out["fused"]
        assert out["phase_split"]["dispatches_per_batch"] == 3.0, \
            out["phase_split"]
        assert out["fit_speedup"] >= gate, (out["fit_speedup"], gate)
        out["gates_passed"] = True
    except AssertionError:
        out["gates_passed"] = False
        raise
    finally:
        line = json.dumps(out)
        print(line, flush=True)
        if json_out:
            with open(json_out, "w") as f:
                f.write(line + "\n")
    return out


def dp_smoke(json_out=None, nbatch=12, batch=32):
    """Tier-1 dp lane: tiny-MLP ``Module.fit`` on the virtual 8-device
    CPU mesh, the whole-step fused SPMD program (multi-context +
    subsumed ``device`` kvstore) vs the kvstore phase-split path.
    Asserts the two load-bearing dp properties — EXACTLY 1 dispatch per
    batch on the fused path (telemetry dispatch counters) and dp-fused
    throughput >= the phase-split path — and banks the JSON artifact
    stamped with the gate outcome
    (a gate-failing round must not read as a healthy record in the
    artifact dir; 5 rounds keeps the tier-1 lane's wall-clock small)."""
    import mxnet_tpu as mx

    n_dev = min(N_DEV, jax.device_count())
    assert n_dev >= 2, "dp-smoke needs the virtual multi-device CPU mesh"
    contexts = [mx.cpu(i) for i in range(n_dev)]
    out, dispatch = _smoke_lane(
        "module_fit_dp_smoke", contexts, "device", rounds=5,
        nbatch=nbatch, batch=batch, speed_key="dp_speedup",
        extra={"n_devices": n_dev}, json_out=None)
    # the dp acceptance gates (ISSUE 2): one program per batch, and the
    # fused SPMD step at least as fast as the kvstore phase-split path
    try:
        assert dispatch[True] == {"train_step": nbatch}, dispatch[True]
        assert out["fused"]["dispatches_per_batch"] == 1.0, out
        assert out["fused"]["img_s"] >= out["phase_split"]["img_s"], out
        out["gates_passed"] = True
    except AssertionError:
        out["gates_passed"] = False
        raise
    finally:
        if json_out:
            with open(json_out, "w") as f:
                f.write(json.dumps(out) + "\n")


def _mp_rules():
    from jax.sharding import PartitionSpec as P
    from mxnet_tpu.parallel import PartitionRules
    # every tensor of the lane MLP shards over mp (weights row-wise,
    # biases element-wise) — the per-device parameter footprint drops
    # to ~1/mp of the replicated layout, which the ledger gate below
    # pins
    return PartitionRules([
        (r"fc\d+_weight$", P("mp", None)),
        (r"fc\d+_bias$", P("mp")),
    ])


MP_AXES = {"dp": 2, "mp": 4}


def _mp_ledger_param_bytes(module_kwargs, contexts, batch):
    """Per-device committed parameter bytes of one freshly bound lane
    module, per the buffer LEDGER (the ``param`` kind under the mesh
    context key tracks summed per-shard bytes across devices)."""
    import gc
    import mxnet_tpu as mx
    from mxnet_tpu import telemetry
    from mxnet_tpu.io import DataDesc
    d, c = 16, 4

    def mlp():
        data = mx.sym.Variable("data")
        net = mx.sym.FullyConnected(data, num_hidden=64, name="fc1")
        net = mx.sym.Activation(net, act_type="relu")
        net = mx.sym.FullyConnected(net, num_hidden=c, name="fc2")
        return mx.sym.SoftmaxOutput(net, name="softmax")

    # collect any earlier module's parameter wrappers first: their live
    # ledger charges under the same mesh key would pollute this reading
    gc.collect()
    telemetry.reset()
    mod = mx.mod.Module(mlp(), context=contexts, **(module_kwargs or {}))
    mod.bind(data_shapes=[DataDesc("data", (batch, d))],
             label_shapes=[DataDesc("softmax_label", (batch,))])
    mod.init_params(mx.initializer.Xavier())
    led = telemetry.ledger().get("mesh(%ddev)" % len(contexts), {})
    total = led.get("by_kind", {}).get("param", 0)
    return total / max(len(contexts), 1)


def mp_smoke(json_out=None, nbatch=12, batch=32):
    """Tier-1 mp lane (ISSUE 15): tiny-MLP ``Module.fit`` on the
    8-device CPU mesh laid out as a 2x4 dp x mp mesh with every
    parameter rule-sharded over ``mp``, vs the kvstore phase-split
    path on the same layout. Gates the four load-bearing dp x mp
    properties:

    - EXACTLY 1 fused dispatch per batch (the 2-D layout still ships
      one donated SPMD program);
    - ZERO fused fallbacks (the rules path never silently phase-splits
      — the lane harness raises on any fused-leg fallback and the
      dispatch-count gate re-checks the banked window);
    - params-alive bytes per device ~ 1/mp of the replicated layout,
      per the buffer ledger's committed ``param`` accounting;
    - fused throughput >= the phase-split path."""
    import mxnet_tpu as mx
    from mxnet_tpu import telemetry

    n_dev = min(N_DEV, jax.device_count())
    assert n_dev >= 8, "mp-smoke needs the 8-device virtual CPU mesh"
    contexts = [mx.cpu(i) for i in range(n_dev)]
    mp = MP_AXES["mp"]
    module_kwargs = {"partition_rules": _mp_rules(),
                     "mesh_axes": dict(MP_AXES)}
    out, dispatch = _smoke_lane(
        "module_fit_mp_smoke", contexts, "device", rounds=5,
        nbatch=nbatch, batch=batch, speed_key="mp_speedup",
        extra={"n_devices": n_dev, "mesh_axes": dict(MP_AXES)},
        json_out=None, module_kwargs=module_kwargs)
    # ledger leg: per-device committed param bytes, rules vs replicated
    was_enabled = telemetry.enabled()
    telemetry.enable()
    try:
        per_dev_mp = _mp_ledger_param_bytes(module_kwargs, contexts,
                                            batch)
        per_dev_repl = _mp_ledger_param_bytes(None, contexts, batch)
    finally:
        if not was_enabled:
            telemetry.disable()
    ratio = per_dev_mp / per_dev_repl if per_dev_repl else None
    out["ledger"] = {
        "param_bytes_per_device_mp": per_dev_mp,
        "param_bytes_per_device_replicated": per_dev_repl,
        "ratio": None if ratio is None else round(ratio, 4),
        "mp": mp,
    }
    try:
        # 1 dispatch/batch, and the banked fused window saw ONLY the
        # fused program (zero fallbacks: a phase-split batch would add
        # fwd_bwd/opt_update dispatches to the window)
        assert dispatch[True] == {"train_step": nbatch}, dispatch[True]
        assert out["fused"]["dispatches_per_batch"] == 1.0, out
        # per-device param bytes ~ 1/mp of replicated (biases and the
        # tiny fc2 rows leave a little slack above the exact 1/mp)
        assert ratio is not None and ratio <= 1.5 / mp, out["ledger"]
        assert out["fused"]["img_s"] >= out["phase_split"]["img_s"], out
        out["gates_passed"] = True
    except AssertionError:
        out["gates_passed"] = False
        raise
    finally:
        line = json.dumps(out)
        print(line, flush=True)
        if json_out:
            with open(json_out, "w") as f:
                f.write(line + "\n")
    return out


# ---------------------------------------------------------------------------
# dist-smoke: 2-process fused dist_sync + elastic chaos leg (ISSUE 12)
# ---------------------------------------------------------------------------

DIST_D, DIST_C = 16, 4


def _dist_mlp():
    import mxnet_tpu as mx
    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=32, name="fc1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=DIST_C, name="fc2")
    return mx.sym.SoftmaxOutput(net, name="softmax")


def _dist_arg(name, default=None, cast=str):
    if name not in sys.argv:
        return default
    i = sys.argv.index(name) + 1
    if i >= len(sys.argv):
        raise SystemExit("%s: missing value" % name)
    return cast(sys.argv[i])


def dist_child():
    """ONE worker of the dist lane: deterministic global batches, this
    rank's slice fed locally, fused dist_sync Module.fit. Writes a JSON
    result (params as float64 lists so the parent can gate bit-equality
    across ranks and rtol vs the single-process oracle). Run with the
    MXNET_TPU_COORDINATOR trio in the env for the 2-process legs, or
    without it as the single-process oracle."""
    import mxnet_tpu as mx
    from mxnet_tpu import telemetry, dist as mxdist
    from mxnet_tpu.io import DataIter, DataDesc, DataBatch

    json_out = _dist_arg("--json-out")
    nproc = _dist_arg("--dist-nproc", 1, int)
    epochs = _dist_arg("--dist-epochs", 2, int)
    nbatch = _dist_arg("--dist-nbatch", 6, int)
    global_batch = _dist_arg("--dist-global-batch", 32, int)
    seed = _dist_arg("--dist-seed", 1234, int)
    ckpt_dir = _dist_arg("--dist-ckpt")
    rank = mxdist.rank()
    local = global_batch // nproc
    sl = slice(rank * local, (rank + 1) * local)

    rs = np.random.RandomState(seed)
    batches = [(rs.uniform(-1, 1, (global_batch, DIST_D))
                .astype(np.float32),
                rs.randint(0, DIST_C, global_batch).astype(np.float32))
               for _ in range(nbatch)]

    class _It(DataIter):
        def __init__(self):
            super().__init__(local)
            self.i = 0

        @property
        def provide_data(self):
            return [DataDesc("data", (local, DIST_D))]

        @property
        def provide_label(self):
            return [DataDesc("softmax_label", (local,))]

        def reset(self):
            self.i = 0

        def next(self):
            if self.i >= nbatch:
                raise StopIteration
            x, y = batches[self.i]
            self.i += 1
            return DataBatch([mx.nd.array(x[sl])],
                             [mx.nd.array(y[sl])], pad=0)

    telemetry.enable()
    # Xavier draws from numpy's GLOBAL generator — identical init across
    # ranks and across the oracle leg needs an explicit seed (the dist
    # commit also broadcasts rank 0's values, but the oracle leg has no
    # one to broadcast from)
    np.random.seed(seed)
    mgr = None
    if ckpt_dir:
        mgr = mx.CheckpointManager(
            os.path.join(ckpt_dir, "r%d" % rank, "model"), keep_last=3)
    mod = mx.mod.Module(_dist_mlp(), context=mx.cpu())
    metric = mx.metric.Accuracy()
    mod.fit(_It(), eval_metric=metric, num_epoch=epochs,
            kvstore="dist_sync", initializer=mx.initializer.Xavier(),
            optimizer="sgd",
            optimizer_params={"learning_rate": 0.05, "momentum": 0.9},
            checkpoint=mgr)
    reason = mod._fused_fallback_reason
    snap = telemetry.counters()
    params, _ = mod.get_params()
    res = {
        "rank": rank,
        "nproc": nproc,
        "fallback_code": getattr(reason, "code", None),
        "kvstore_dist_fallbacks": snap.get("fused_fallback.kvstore_dist",
                                           0),
        "dist_counters": {k: int(v) for k, v in snap.items()
                          if k.startswith(("kvstore.dist", "elastic"))},
        "acc": metric.get()[1],
        "finite": bool(all(
            np.isfinite(np.asarray(v.asnumpy())).all()
            for v in params.values())),
        "params": {k: np.asarray(v.asnumpy(), np.float64).tolist()
                   for k, v in sorted(params.items())},
        "completed": True,
    }
    if json_out:
        with open(json_out, "w") as f:
            json.dump(res, f)
    mxdist.finalize()
    print("dist child rank=%d done" % rank, flush=True)


def _dist_child_main():
    import traceback
    try:
        dist_child()
    except BaseException:
        traceback.print_exc()
        sys.stderr.flush()
        from mxnet_tpu import dist as mxdist
        if mxdist.initialized():
            # die WITHOUT destructors: a crashing worker that tears
            # down its coordination client drags every survivor into
            # the fatal shutdown barrier — exactly what the elastic
            # tier exists to avoid
            mxdist.abort(DIST_FAULT_RC)
        raise


def dist_smoke(json_out=None):
    """Tier-1 dist lane: real 2-process ``dist_sync`` on one box
    (``jax.distributed`` over localhost, gloo CPU collectives).

    Leg A (fused): both workers run the fused donated-buffer train step
    over the process-spanning dp mesh — gates zero ``kvstore_dist``
    fallback events and BIT-EQUAL params across ranks.
    Leg B (oracle): a single-process run at the same global batch —
    gates params equal at rtol=1e-5 (the cross-host psum reassociates
    the batch reduction; bit-equality is reported, not required).
    Leg C (chaos): rank 1 is killed deterministically mid-epoch by an
    injected ``kv_collective`` fault — gates that rank 0 detects the
    death via the liveness gate, re-meshes, resumes from the last
    atomic checkpoint, FINISHES the run (exit 0, finite params,
    elastic counters), and that the postmortem names rank 1 and parses
    via tools/flight_view.py. Every leg runs under a hard timeout: a
    hung process fails the lane."""
    import shutil
    import socket
    import subprocess
    import tempfile

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    work = tempfile.mkdtemp(prefix="mxtpu-dist-smoke-")
    out = {"lane": "module_fit_dist_smoke", "platform": "cpu"}
    epochs, nbatch, gbatch = 2, 6, 32

    def _free_port():
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
        s.close()
        return port

    def _spawn(tag, rank, nproc, port, args, env_extra):
        env = dict(os.environ, JAX_PLATFORMS="cpu", MXNET_TELEMETRY="1")
        env.pop("XLA_FLAGS", None)
        env.pop("MXNET_FAULTS", None)
        hb = os.path.join(work, "hb-%s" % tag)
        os.makedirs(hb, exist_ok=True)
        if nproc > 1:
            env.update({
                "MXNET_TPU_COORDINATOR": "127.0.0.1:%d" % port,
                "MXNET_TPU_NUM_PROCESSES": str(nproc),
                "MXNET_TPU_PROCESS_ID": str(rank),
                "MXTPU_HEARTBEAT_DIR": hb,
                # 15 beats of staleness margin: a share-throttled box
                # can gap a beat thread well past one interval
                "MXTPU_HEARTBEAT_INTERVAL": "0.2",
                "MXTPU_HEARTBEAT_TIMEOUT": "3.0",
                "MXTPU_GATE_TIMEOUT": "60",
            })
        env.update(env_extra)
        jout = os.path.join(work, "%s-r%d.json" % (tag, rank))
        cmd = [sys.executable,
               os.path.join(root, "tools", "module_fit_probe.py"),
               "--dist-child", "--json-out", jout,
               "--dist-nproc", str(nproc), "--dist-epochs", str(epochs),
               "--dist-nbatch", str(nbatch),
               "--dist-global-batch", str(gbatch)] + args
        log = open(os.path.join(work, "%s-r%d.log" % (tag, rank)), "wb")
        p = subprocess.Popen(cmd, stdout=log, stderr=log, env=env,
                             cwd=root)
        p._mxtpu_json = jout
        p._mxtpu_log = log
        return p

    def _leg(tag, procs, timeout_s):
        """Wait for every proc under ONE deadline; kill stragglers —
        a hung worker is a lane FAILURE, never a hung lane."""
        import time as _time
        deadline = _time.monotonic() + timeout_s
        rcs, results = [], []
        try:
            for p in procs:
                left = max(1.0, deadline - _time.monotonic())
                try:
                    p.wait(timeout=left)
                except subprocess.TimeoutExpired:
                    p.kill()
                    p.wait()
                    raise SystemExit(
                        "dist-smoke[%s]: worker hung past %ds (killed); "
                        "logs under %s" % (tag, timeout_s, work))
        finally:
            for p in procs:
                if p.poll() is None:
                    p.kill()
                p._mxtpu_log.close()
        for p in procs:
            rcs.append(p.returncode)
            try:
                with open(p._mxtpu_json) as f:
                    results.append(json.load(f))
            except (OSError, ValueError):
                results.append(None)
        return rcs, results

    try:
        # -- leg A: 2-process fused dist_sync ---------------------------
        port = _free_port()
        procs = [_spawn("fused", r, 2, port, [], {}) for r in (0, 1)]
        rcs, res = _leg("fused", procs, 240)
        a0, a1 = res
        out["fused"] = {
            "rcs": rcs,
            "fallback_codes": [r and r["fallback_code"] for r in res],
            "kvstore_dist_fallbacks": [
                r["kvstore_dist_fallbacks"] if r else None for r in res],
            "dist_counters": a0 and a0["dist_counters"],
            "acc": [r and r["acc"] for r in res],
        }

        # -- leg B: single-process oracle, same global batch ------------
        procs = [_spawn("single", 0, 1, 0, [], {})]
        rcs_s, res_s = _leg("single", procs, 180)
        single = res_s[0]
        out["single"] = {"rcs": rcs_s, "acc": single and single["acc"]}

        # -- leg C: chaos — kill rank 1 mid-epoch, rank 0 recovers ------
        # gate crossings before the steps: one kv-channel crossing per
        # broadcasting kv.init call (the probe net has 4 params —
        # fc1/fc2 weight+bias — initialised one call each) + one
        # step-channel crossing at the first dist commit (both added
        # by the mxsync collective-discipline fixes), then one step
        # crossing per fused step — nbatch gens per epoch.
        # n = 5 + nbatch + 3 dies in epoch 1 at batch index 2, AFTER
        # the epoch-0-end checkpoint exists
        chaos_epochs = 3
        fault_n = 5 + nbatch + 3
        # ONE flight dir shared by both ranks (the fleet posture:
        # rank-stamped filenames keep the artifacts apart) — rank 0's
        # dead_worker dump, rank 1's worker_abort dump and the series
        # JSONLs all land here for the merged cluster view
        flight = os.path.join(work, "flight")
        os.makedirs(flight, exist_ok=True)
        ckpt = os.path.join(work, "ckpt")
        port = _free_port()
        epochs = chaos_epochs
        procs = [
            _spawn("chaos", 0, 2, port, ["--dist-ckpt", ckpt],
                   {"MXNET_FLIGHT_DIR": flight,
                    "MXNET_METRICS_INTERVAL_MS": "200"}),
            # rank 1 is first a STRAGGLER (every dispatch delayed),
            # then DIES at the deterministic crossing. A dispatch-side
            # delay is INVISIBLE to gate arrival order — rank 0 absorbs
            # it blocked in the previous step's completion await, so
            # both ranks reach the next gate together — which is
            # exactly what the self-time half of the verdict exists
            # for: rank 1 publishes ~delay more own-work time per
            # crossing and the streak machine must emit dist.straggler
            # naming it. 250 ms keeps the published skew well clear of
            # the 50 ms threshold even when rank 0 does epoch-boundary
            # work (checkpoint, eval) inside the same window.
            _spawn("chaos", 1, 2, port, ["--dist-ckpt", ckpt],
                   {"MXNET_FLIGHT_DIR": flight,
                    "MXNET_METRICS_INTERVAL_MS": "200",
                    "MXNET_FAULTS":
                        "dispatch:delay=250:first=50;"
                        "kv_collective:raise:n=%d" % fault_n}),
        ]
        rcs_c, res_c = _leg("chaos", procs, 300)
        c0 = res_c[0]
        pms = sorted(f for f in os.listdir(flight)
                     if f.endswith("dead_worker.json"))
        pm_summary = None
        if pms:
            view = subprocess.run(
                [sys.executable,
                 os.path.join(root, "tools", "flight_view.py"),
                 os.path.join(flight, pms[0]), "--json"],
                stdout=subprocess.PIPE, text=True, timeout=60, cwd=root)
            if view.returncode == 0:
                pm_summary = json.loads(view.stdout)
        # the merged cluster view: every rank's dump joined, clocks
        # aligned from matched gate crossings, ONE artifact (ISSUE 18)
        fleet_trace = os.path.join(work, "chaos-fleet-trace.json")
        fleet = subprocess.run(
            [sys.executable,
             os.path.join(root, "tools", "fleet_view.py"),
             flight, "--json", "--trace", fleet_trace],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            timeout=60, cwd=root)
        fleet_summary = None
        if fleet.returncode == 0:
            fleet_summary = json.loads(fleet.stdout)
        out["chaos"] = {
            "rcs": rcs_c,
            "survivor": c0 and {
                "completed": c0["completed"], "finite": c0["finite"],
                "elastic": c0["dist_counters"]},
            "postmortems": pms,
            "postmortem_extra": pm_summary and pm_summary.get("extra"),
            "fleet_rc": fleet.returncode,
            "fleet": fleet_summary and {
                "n_ranks": fleet_summary["n_ranks"],
                "dead_ranks": fleet_summary["dead_ranks"],
                "stragglers": fleet_summary["stragglers"],
                "clock": fleet_summary["clock"],
                "warnings": fleet_summary["warnings"]},
        }

        # -- gates ------------------------------------------------------
        try:
            # A: fused across processes, zero dist fallbacks, replicas
            # bit-equal
            assert rcs == [0, 0], out["fused"]
            assert all(r and r["completed"] for r in res), out["fused"]
            assert [r["fallback_code"] for r in res] == [None, None], \
                out["fused"]
            assert [r["kvstore_dist_fallbacks"] for r in res] == [0, 0], \
                out["fused"]
            assert a0["dist_counters"].get("kvstore.dist.fused_steps") \
                == 2 * nbatch, a0["dist_counters"]
            bit_equal_ranks = all(
                np.array_equal(np.array(a0["params"][k]),
                               np.array(a1["params"][k]))
                for k in a0["params"])
            assert bit_equal_ranks, "replicas diverged across ranks"
            # B: matches the single-process oracle at the same global
            # batch (psum reassociation noise only)
            assert rcs_s == [0] and single and single["completed"]
            max_abs = max(
                float(np.abs(np.array(a0["params"][k])
                             - np.array(single["params"][k])).max())
                for k in a0["params"])
            out["oracle_max_abs_diff"] = max_abs
            out["oracle_bit_equal"] = all(
                np.array_equal(np.array(a0["params"][k]),
                               np.array(single["params"][k]))
                for k in a0["params"])
            assert all(
                np.allclose(np.array(a0["params"][k]),
                            np.array(single["params"][k]),
                            rtol=1e-5, atol=1e-6)
                for k in a0["params"]), "2-proc vs single: %r" % max_abs
            # C: deterministic kill, detected, re-meshed, resumed,
            # finished; postmortem names rank 1
            assert rcs_c[1] == DIST_FAULT_RC, rcs_c
            assert rcs_c[0] == 0, rcs_c
            assert c0 and c0["completed"] and c0["finite"], out["chaos"]
            el = c0["dist_counters"]
            assert el.get("elastic.dead_workers") == 1, el
            assert el.get("elastic.remesh") == 1, el
            assert el.get("elastic.resumed") == 1, el
            assert pms, "no dead_worker postmortem written"
            assert pm_summary is not None, "flight_view failed to parse"
            extra = pm_summary["extra"]
            assert extra["dead_ranks"] == [1], extra
            assert extra["epoch"] == 1 and extra["nbatch"] == 2, extra
            # C (fleet): ONE merged cluster view over the shared
            # flight dir — the killed rank is named dead, the
            # pre-death gate-wait spike is attributed to IT (rank 0's
            # dispatch ran undelayed, so every excess wait blames
            # rank 1), clocks align to within one gate-poll interval
            # (same box: the solved offset must be ~0), and the
            # survivor's dump carries the victim's own postmortem
            assert fleet.returncode == 0, fleet.stderr
            assert fleet_summary["n_ranks"] >= 2, fleet_summary
            assert fleet_summary["dead_ranks"] == [1], fleet_summary
            stragglers = fleet_summary["stragglers"]
            assert stragglers and stragglers[0]["rank"] == 1, stragglers
            assert stragglers[0]["straggler_events"] > 0, stragglers
            offs = fleet_summary["clock"]["offsets_s"]
            assert all(abs(o) <= 0.25 for o in offs.values()), offs
            assert any(int(m) > 0 for r, m in
                       fleet_summary["clock"]["matched_crossings"]
                       .items() if int(r) != 0), fleet_summary["clock"]
            with open(fleet_trace) as f:
                trace = json.load(f)
            tracks = {e["pid"] for e in trace["traceEvents"]
                      if e.get("name") == "process_name"}
            assert tracks >= {0, 1}, tracks
            peers = extra.get("peer_postmortems") or []
            assert any(p["rank"] == 1 and p["reason"] == "worker_abort"
                       for p in peers), peers
            out["gates_passed"] = True
        except AssertionError:
            out["gates_passed"] = False
            raise
    finally:
        # params are bulky and served their purpose — keep the artifact
        # readable
        line = json.dumps(out)
        print(line, flush=True)
        if json_out:
            with open(json_out, "w") as f:
                f.write(line + "\n")
        if out.get("gates_passed"):
            shutil.rmtree(work, ignore_errors=True)
        else:
            print("dist-smoke: logs kept under %s" % work, flush=True)
    return out


def _json_out_arg():
    if "--json-out" not in sys.argv:
        return None
    i = sys.argv.index("--json-out") + 1
    if i >= len(sys.argv) or sys.argv[i].startswith("--"):
        raise SystemExit("--json-out: missing output path")
    return sys.argv[i]


if __name__ == "__main__":
    if DIST_CHILD:
        _dist_child_main()
    elif DIST_SMOKE:
        dist_smoke(json_out=_json_out_arg())
    elif MP_SMOKE:
        mp_smoke(json_out=_json_out_arg())
    elif DP_SMOKE:
        dp_smoke(json_out=_json_out_arg())
    elif FIT_SMOKE:
        fit_smoke(json_out=_json_out_arg())
    else:
        main()
