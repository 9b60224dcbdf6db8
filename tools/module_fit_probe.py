#!/usr/bin/env python3
"""The count-only tier-1 lanes of the user-facing ``Module.fit`` path.

Every lane runs on XLA's CPU backend, prints ONE JSON object (and writes
it to ``--json-out``) and exits 0 whenever it ran to the end: counts,
bytes and equality, never a clock. ``tests/test_module_fit_lane.py`` and
``tests/test_dist_lane.py`` hold each property as a test of its own. A
rate is read on the chip by ``benchmarks/run.py`` and nowhere else.

Fit-smoke lane:     python tools/module_fit_probe.py --fit-smoke \
                        [--json-out PATH]
  (tiny-MLP Module.fit, 20 batches: the fused whole-step program against
  the phase-split oracle: jitted-program dispatches per batch of each
  leg, the fall-back code of each leg, parameters bit-equal)
DP-smoke lane:      python tools/module_fit_probe.py --dp-smoke \
                        [--json-out PATH]
  (the same on the virtual 8-device CPU mesh: the fused SPMD
  data-parallel step against the kvstore phase-split path)
MP-smoke lane:      python tools/module_fit_probe.py --mp-smoke \
                        [--json-out PATH]
  (the same mesh laid out 2x4 dp x mp with every parameter rule-sharded
  over mp (parallel.partition.PartitionRules): beside the counts, the
  buffer ledger's committed param bytes per device against the
  replicated layout's)
Dist-smoke lane:    python tools/module_fit_probe.py --dist-smoke \
                        [--json-out PATH]
  (two real processes under ``dist_sync``, a single-process oracle and
  a chaos leg; see ``dist_smoke``)
"""
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from lane_common import emit, force_cpu_devices, main

# a dist child that dies on an injected fault exits THROUGH
# mx.dist.abort with this code (destructor-free death: a crashing
# worker must not drag survivors into the coordination shutdown
# barrier); the lane's test holds the victim to it
DIST_FAULT_RC = 21
N_DEV = 8

if "--dp-smoke" in sys.argv or "--mp-smoke" in sys.argv:
    force_cpu_devices(N_DEV)

import numpy as np
import jax

jax.config.update("jax_platforms", "cpu")

LANE_D, LANE_C = 16, 4


def _lane_mlp():
    import mxnet_tpu as mx
    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=64, name="fc1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=LANE_C, name="fc2")
    return mx.sym.SoftmaxOutput(net, name="softmax")


def _smoke_lane(lane, contexts, kvstore, nbatch, batch, extra=None,
                module_kwargs=None):
    """The ONE harness the fit, dp and mp lanes share: tiny-MLP
    ``Module.fit`` from one seed on the same batches, once through the
    fused whole-step program and once through the phase-split oracle.
    Each leg trains a warm epoch (bind, init, compile) and then one
    epoch inside a clean ``mx.telemetry`` window, whose jitted-program
    dispatch counts are what the lane reports, with each leg's
    fall-back code and whether the two legs' parameters came out
    bit-equal."""
    import mxnet_tpu as mx
    from mxnet_tpu import telemetry
    from mxnet_tpu.io import DataIter, DataDesc, DataBatch

    rs = np.random.RandomState(0)
    batches = [(rs.uniform(-1, 1, (batch, LANE_D)).astype(np.float32),
                rs.randint(0, LANE_C, batch).astype(np.float32))
               for _ in range(nbatch)]

    class _PreslicedIter(DataIter):
        """Device-resident pre-sliced batches: the lane counts what the
        framework dispatches a batch, not numpy slicing."""

        def __init__(self):
            super().__init__(batch)
            self._batches = [DataBatch([mx.nd.array(x)], [mx.nd.array(y)],
                                       pad=0) for x, y in batches]
            self.i = 0

        @property
        def provide_data(self):
            return [DataDesc("data", (batch, LANE_D))]

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

    opt_params = {"learning_rate": 0.05, "momentum": 0.9}

    def leg(fused):
        os.environ["MXNET_MODULE_FUSED_STEP"] = "1" if fused else "0"
        # Xavier draws from numpy's GLOBAL generator: both legs start
        # from the same parameters
        np.random.seed(0)
        mod = mx.mod.Module(_lane_mlp(), context=contexts,
                            **(module_kwargs or {}))
        metric = mx.metric.Accuracy()
        train = _PreslicedIter()
        # warm epoch: bind + init + compile land outside the window
        mod.fit(train, eval_metric=metric, num_epoch=1, kvstore=kvstore,
                initializer=mx.initializer.Xavier(),
                optimizer="sgd", optimizer_params=opt_params)
        # clean registry window: the counts read after this epoch
        # describe THIS epoch alone
        telemetry.reset()
        mod.fit(train, eval_metric=metric, num_epoch=1, kvstore=kvstore,
                optimizer="sgd", optimizer_params=opt_params)
        metric.get()
        params, _ = mod.get_params()
        report = {
            "fallback_code": getattr(mod._fused_fallback_reason, "code",
                                     None),
            "dispatch_counts": telemetry.dispatch_counts(),
        }
        return report, {k: v.asnumpy() for k, v in params.items()}

    # the lane's accounting READS the registry, so recording must be on
    # for its window regardless of the ambient MXNET_TELEMETRY pin
    # (restored after: the lane must not flip the session's state)
    was_enabled = telemetry.enabled()
    telemetry.enable()
    try:
        fused, params_f = leg(True)
        split, params_s = leg(False)
    finally:
        if not was_enabled:
            telemetry.disable()

    out = {"lane": lane}
    out.update(extra or {})
    out.update({
        "nbatch": nbatch,
        "fused": fused, "phase_split": split,
        "params_bit_equal": sorted(params_f) == sorted(params_s) and all(
            np.array_equal(params_f[k], params_s[k]) for k in params_f),
    })
    return out


def fit_smoke(json_out=None, nbatch=20, batch=32):
    """Tier-1 fit lane: tiny-MLP ``Module.fit`` on one CPU device, the
    fused whole-step program against the phase-split oracle."""
    import mxnet_tpu as mx
    return emit(_smoke_lane("module_fit_smoke", mx.cpu(), "local",
                             nbatch=nbatch, batch=batch), json_out)


def dp_smoke(json_out=None, nbatch=12, batch=32):
    """Tier-1 dp lane: tiny-MLP ``Module.fit`` on the virtual 8-device
    CPU mesh, the whole-step fused SPMD program (multi-context +
    subsumed ``device`` kvstore) against the kvstore phase-split path."""
    import mxnet_tpu as mx

    n_dev = min(N_DEV, jax.device_count())
    assert n_dev >= 2, "dp-smoke needs the virtual multi-device CPU mesh"
    contexts = [mx.cpu(i) for i in range(n_dev)]
    return emit(_smoke_lane("module_fit_dp_smoke", contexts, "device",
                             nbatch=nbatch, batch=batch,
                             extra={"n_devices": n_dev}), json_out)


def _mp_rules():
    from jax.sharding import PartitionSpec as P
    from mxnet_tpu.parallel import PartitionRules
    # every tensor of the lane MLP shards over mp (weights row-wise,
    # biases element-wise): the per-device parameter footprint drops
    # to ~1/mp of the replicated layout, which the ledger reading shows
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

    # collect any earlier module's parameter wrappers first: their live
    # ledger charges under the same mesh key would pollute this reading
    gc.collect()
    telemetry.reset()
    mod = mx.mod.Module(_lane_mlp(), context=contexts,
                        **(module_kwargs or {}))
    mod.bind(data_shapes=[DataDesc("data", (batch, LANE_D))],
             label_shapes=[DataDesc("softmax_label", (batch,))])
    mod.init_params(mx.initializer.Xavier())
    led = telemetry.ledger().get("mesh(%ddev)" % len(contexts), {})
    total = led.get("by_kind", {}).get("param", 0)
    return total / max(len(contexts), 1)


def mp_smoke(json_out=None, nbatch=12, batch=32):
    """Tier-1 mp lane (ISSUE 15): tiny-MLP ``Module.fit`` on the
    8-device CPU mesh laid out as a 2x4 dp x mp mesh with every
    parameter rule-sharded over ``mp``, against the kvstore phase-split
    path on the same layout; then the ledger leg: committed ``param``
    bytes per device under the rules against the replicated layout."""
    import mxnet_tpu as mx
    from mxnet_tpu import telemetry

    n_dev = min(N_DEV, jax.device_count())
    assert n_dev >= 8, "mp-smoke needs the 8-device virtual CPU mesh"
    contexts = [mx.cpu(i) for i in range(n_dev)]
    module_kwargs = {"partition_rules": _mp_rules(),
                     "mesh_axes": dict(MP_AXES)}
    out = _smoke_lane(
        "module_fit_mp_smoke", contexts, "device", nbatch=nbatch,
        batch=batch, extra={"n_devices": n_dev,
                            "mesh_axes": dict(MP_AXES)},
        module_kwargs=module_kwargs)
    was_enabled = telemetry.enabled()
    telemetry.enable()
    try:
        per_dev_mp = _mp_ledger_param_bytes(module_kwargs, contexts,
                                            batch)
        per_dev_repl = _mp_ledger_param_bytes(None, contexts, batch)
    finally:
        if not was_enabled:
            telemetry.disable()
    out["ledger"] = {
        "param_bytes_per_device_mp": per_dev_mp,
        "param_bytes_per_device_replicated": per_dev_repl,
        "mp": MP_AXES["mp"],
    }
    return emit(out, json_out)


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
    over the process-spanning dp mesh: ``kvstore_dist`` fallback events,
    fused steps counted, params compared bit for bit across ranks.
    Leg B (oracle): a single-process run at the same global batch:
    params compared at rtol=1e-5 (the cross-host psum reassociates
    the batch reduction; bit-equality is reported, not required).
    Leg C (chaos): rank 1 is killed deterministically mid-epoch by an
    injected ``kv_collective`` fault: rank 0 is to detect the death via
    the liveness gate, re-mesh, resume from the last atomic checkpoint
    and FINISH the run (exit 0, finite params, elastic counters), and
    the postmortem is to name rank 1 and parse via
    tools/flight_view.py. ``tests/test_dist_lane.py`` holds each of
    these over the lane's JSON. Every leg runs under a hard timeout: a
    hung process fails the lane."""
    import shutil
    import socket
    import subprocess
    import tempfile

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    work = tempfile.mkdtemp(prefix="mxtpu-dist-smoke-")
    out = {"lane": "module_fit_dist_smoke"}
    epochs, nbatch, gbatch = 2, 6, 32
    ran_to_end = False

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
        }

        # -- leg B: single-process oracle, same global batch ------------
        procs = [_spawn("single", 0, 1, 0, [], {})]
        rcs_s, res_s = _leg("single", procs, 180)
        single = res_s[0]
        out["single"] = {"rcs": rcs_s}

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
                "clock": fleet_summary["clock"]},
        }

        # -- what the lane's tests compare --------------------------------
        def _params_equal(a, b, **tol):
            if not (a and b):
                return None
            same = np.allclose if tol else np.array_equal
            return all(same(np.array(a["params"][k]),
                            np.array(b["params"][k]), **tol)
                       for k in a["params"])

        out["fused"]["completed"] = [bool(r and r["completed"])
                                     for r in res]
        out["fused"]["steps_expected"] = 2 * nbatch
        # A: replicas bit-equal across the two ranks
        out["ranks_bit_equal"] = _params_equal(a0, a1)
        # B: the single-process oracle at the same global batch (the
        # cross-host psum reassociates the batch reduction, so close
        # and not bit-equal)
        out["single"]["completed"] = bool(single and single["completed"])
        out["oracle_allclose"] = _params_equal(a0, single,
                                               rtol=1e-5, atol=1e-6)
        out["oracle_max_abs_diff"] = a0 and single and max(
            float(np.abs(np.array(a0["params"][k])
                         - np.array(single["params"][k])).max())
            for k in a0["params"])
        # C: the merged trace holds a track for each rank
        out["chaos"]["fault_rc"] = DIST_FAULT_RC
        out["chaos"]["fleet_stderr"] = fleet.stderr[-1000:] \
            if fleet.returncode else ""
        try:
            with open(fleet_trace) as f:
                trace = json.load(f)
            out["chaos"]["trace_tracks"] = sorted(
                {e["pid"] for e in trace["traceEvents"]
                 if e.get("name") == "process_name"})
        except (OSError, ValueError):
            out["chaos"]["trace_tracks"] = None
        ran_to_end = True
    finally:
        # params are bulky and served their purpose: keep the JSON
        # readable
        emit(out, json_out)
        if ran_to_end:
            shutil.rmtree(work, ignore_errors=True)
        else:
            print("dist-smoke: logs kept under %s" % work, flush=True)
    return out


if __name__ == "__main__":
    main({"--dist-child": lambda json_out: _dist_child_main(),
          "--dist-smoke": dist_smoke, "--mp-smoke": mp_smoke,
          "--dp-smoke": dp_smoke, "--fit-smoke": fit_smoke},
         children=("--dist-child",))
