"""The ``fit`` window: ``Module.fit`` driven from a seeded pool of batches.

Set-up builds ONE Module, feeds it the benchmark's own weights, drives
it through its first steps with ``fit`` (the steps the reference follows)
and hands the same Module to the timed ``fit`` call. Nothing of the
model's size or name is decided here: symbol, shapes, batch, chips and
kvstore come from the configuration's and the traffic mix's files.
"""
import gc
import os
import queue
import threading
import time

import numpy as np

from benchmarks.harness import inputs
from benchmarks.harness.files import ROOT, load_file


class BenchFailure(Exception):
    """The run cannot give a result (no chip, a check of the path)."""


# -- the model as the program sees it ----------------------------------------

def build_symbol(cfg, rehearse):
    s = cfg["symbol"]
    kwargs = dict(s["kwargs"])
    if rehearse:
        kwargs.update(cfg["rehearse"]["symbol_kwargs"])
    fn = getattr(load_file(s["file"], "bench_symbol"), s["function"])
    return fn(**kwargs)


def shapes_of(sym, cfg, dtypes, batch, rehearse):
    """By name: each parameter's ``(shape, dtype)`` as the program's type
    inference places it (bf16 data leaves the batch-norm scales float32),
    the auxiliary states' shapes, and every node's output shape (for the
    FLOP count), at ``batch`` rows."""
    import json
    image = cfg["rehearse"]["image_shape"] if rehearse else cfg["image_shape"]
    s = cfg["symbol"]
    known = {s["data_name"]: (batch,) + tuple(image),
             s["label_name"]: (batch,)}
    arg_shapes, _, aux_shapes = sym.infer_shape(**known)
    arg_types, _, _ = sym.infer_type(**{s["data_name"]: dtypes["data"],
                                        s["label_name"]: dtypes["label"]})
    params = {k: (tuple(v), str(np.dtype(t))) for k, v, t in
              zip(sym.list_arguments(), arg_shapes, arg_types)
              if k not in known}
    aux = {k: tuple(v) for k, v in
           zip(sym.list_auxiliary_states(), aux_shapes)}
    nodes = json.loads(sym.tojson())["nodes"]
    internals = sym.get_internals()
    _, out_shapes, _ = internals.infer_shape(**known)
    node_shapes = {}
    for name, shape in zip(internals.list_outputs(), out_shapes):
        node_shapes[name[:-len("_output")] if name.endswith("_output")
                    else name] = tuple(shape)
    return known, params, aux, nodes, node_shapes


# -- the feed ----------------------------------------------------------------

def make_iter(pool, descs, count=None, seconds=None, offset=0):
    """A ``DataIter`` over ``pool`` round-robin from batch ``offset``: it
    hands out ``count`` batches, or goes on until ``seconds`` have passed
    since the first batch was asked for."""
    import jax
    from mxnet_tpu.io import DataBatch, DataDesc, DataIter

    (dname, dshape, ddtype), (lname, lshape) = descs

    class PoolIter(DataIter):
        def __init__(self):
            super().__init__(dshape[0])
            self.i = 0
            self.first_request = None

        @property
        def provide_data(self):
            return [DataDesc(dname, dshape, dtype=ddtype)]

        @property
        def provide_label(self):
            return [DataDesc(lname, lshape)]

        def reset(self):
            pass

        def next(self):
            with jax.profiler.TraceAnnotation("feed.next"):
                now = time.perf_counter()
                if self.first_request is None:
                    self.first_request = now
                if (self.i >= count if count is not None
                        else now - self.first_request >= seconds):
                    raise StopIteration
                x, y = pool[(offset + self.i) % len(pool)]
                self.i += 1
                return DataBatch([x], [y], pad=0)

    return PoolIter()


class Watcher:
    """Waits for each step's held output in dispatch order and stamps the
    host clock; the fit loop itself is never blocked."""

    def __init__(self):
        self.q = queue.Queue()
        self.done = []
        self.error = None
        self.thread = threading.Thread(target=self._run, daemon=True,
                                       name="bench-watcher")
        self.thread.start()

    def _run(self):
        while True:
            out = self.q.get()
            if out is None:
                return
            try:
                out.block_until_ready()
            except Exception as e:     # reported by close()
                self.error = e
                return
            self.done.append(time.perf_counter())

    def put(self, out):
        self.q.put(out)

    def close(self, timeout=600):
        self.q.put(None)
        self.thread.join(timeout)
        if self.thread.is_alive():
            raise BenchFailure("the watcher did not drain in %d s" % timeout)
        if self.error is not None:
            raise self.error
        return self.done


# -- what the program's first steps read -------------------------------------

def _first_shard(raw):
    """A replicated array's copy on its first device."""
    shards = raw.addressable_shards
    if len(shards) > 1 and shards[0].data.shape == raw.shape:
        return shards[0].data
    return raw


def effective_updater(mod):
    kv = mod._kvstore
    return kv._updater if (kv is not None and mod._update_on_kvstore) \
        else mod._updater


def optimizer_state(mod):
    """``{name: (momentum, float32 master)}`` as raw device arrays."""
    states = effective_updater(mod).states
    out = {}
    for i, name in enumerate(mod._param_names):
        st = states.get(i)
        if st is None:
            continue
        # multi-precision leaves hold (momentum, float32 master); leaves
        # that are float32 already (batch-norm scales) are their own master
        inner, w32 = st if isinstance(st, tuple) \
            else (st, mod._exec.arg_dict[name])
        out[name] = (_first_shard(inner._data), _first_shard(w32._data))
    return out


def program_readings(mod, opt, losses_of, w0, aux0, holder):
    """Callbacks and a finisher that read, around the warm-up steps, what
    the reference's steps are compared with."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32

    @jax.jit
    def norms(tree):
        return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(f32))))
                for k, v in tree.items()}

    @jax.jit
    def diff_norms(a, b):
        return {k: jnp.sqrt(jnp.sum(jnp.square(a[k].astype(f32)
                                               - b[k].astype(f32))))
                for k in a}

    def after_first_step():
        # momentum after one step from zero is -lr * rescale_grad * grad
        # (wd is 0): the first gradient as the optimizer got it
        mom = {k: v[0] for k, v in optimizer_state(mod).items()}
        holder["mom_norms"] = norms(mom)

    def finish():
        lr = opt["learning_rate"]
        master = {k: v[1] for k, v in optimizer_state(mod).items()}
        aux = {k: _first_shard(a._data)
               for k, a in mod._exec.aux_dict.items()}
        change = diff_norms(master, {k: w0[k] for k in master})
        aux_change = diff_norms(aux, {k: aux0[k] for k in aux})
        return dict(
            losses=losses_of(holder["outs"]),
            grad_norms={k: float(v) / lr
                        for k, v in holder["mom_norms"].items()},
            change_norms={k: float(v) for k, v in change.items()},
            aux_change_norms={k: float(v) for k, v in aux_change.items()})

    return after_first_step, finish


# -- the window --------------------------------------------------------------

def run(cell, cfg, traffic, args, harness):
    """Set up, measure, check. Returns the pieces ``run.py`` prints."""
    t_process = harness["t_process"]
    rehearse = args.rehearse
    marks_s = {}        # where set-up's seconds go, for ``info``

    def mark(name):
        marks_s[name] = time.perf_counter() - t_process

    import jax
    import jax.numpy as jnp
    mark("import_jax")
    devices = jax.devices()
    mark("backend")
    dev = devices[0]
    chips = int(cell["chips"])
    if not rehearse and dev.platform != "tpu":
        raise BenchFailure("no accelerator: jax.devices()[0] is %r" % (dev,))
    if len(devices) < chips:
        raise BenchFailure("cell asks for %d chips, JAX reports %d device(s)"
                           % (chips, len(devices)))
    if int(traffic["chips"]) != chips:
        raise BenchFailure("traffic mix is for %s chips, the cell for %d"
                           % (traffic["chips"], chips))

    import mxnet_tpu as mx
    from mxnet_tpu import jax_cache, telemetry

    mark("import_mxnet_tpu")
    os.environ.pop("MXNET_COMPILE_CACHE", None)
    cache_dir = jax_cache.place()
    # JAX's default keeps programs that compiled in under a second out of
    # the cache; some ninety small ones are built on the way to the first
    # step, so every run would compile them again
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    watch = jax_cache.CacheWatch()

    per_chip = int((traffic["rehearse"] if rehearse else traffic)
                   ["batch_per_chip"])
    batch = per_chip * chips
    sym = build_symbol(cfg, rehearse)
    s = cfg["symbol"]
    dtypes = dict(cfg["dtypes"])
    if rehearse:
        dtypes.update(cfg["rehearse"].get("dtypes", {}))
    classes = (cfg["rehearse"] if rehearse else cfg)["num_classes"]
    known, pshapes, ashapes, nodes, node_shapes = shapes_of(
        sym, cfg, dtypes, batch, rehearse)

    # weights in the type they are computed in (the float32 masters are
    # made from them by the optimizer), statistics in float32
    w0 = inputs.make_weights(args.seed, pshapes)
    aux0 = inputs.make_weights(args.seed, {k: (v, "float32")
                                           for k, v in ashapes.items()})
    n_pool = int(traffic["pool_batches"])
    warm = int(traffic["warmup_steps"])
    compared = int(traffic["compared_steps"])
    if compared > warm or warm > n_pool:
        raise BenchFailure("compared_steps <= warmup_steps <= pool_batches")
    pool = inputs.make_pool(args.seed, n_pool, known[s["data_name"]],
                            classes, dtypes["data"])

    mark("weights_and_pool")

    def ctx(i):
        return mx.cpu(i) if dev.platform == "cpu" else mx.tpu(i)

    contexts = [ctx(i) for i in range(chips)]
    mod = mx.mod.Module(sym, data_names=(s["data_name"],),
                        label_names=(s["label_name"],),
                        context=contexts if chips > 1 else contexts[0])
    o = dict(cfg["optimizer"])
    if rehearse:
        o.update(cfg["rehearse"].get("optimizer", {}))
    opt = {"learning_rate": o["learning_rate"], "momentum": o["momentum"],
           "wd": o["wd"], "multi_precision": o["multi_precision"],
           "rescale_grad": 1.0 / batch}
    descs = ((s["data_name"], known[s["data_name"]],
              np.dtype(jnp.dtype(dtypes["data"]))),
             (s["label_name"], known[s["label_name"]]))
    # the Module gets copies: wrapping and ``copyto`` on one device alias
    # the buffer, and the fused step donates what it is given
    copies = jax.jit(lambda t: jax.tree.map(jnp.copy, t))
    arg_params = {k: mx.nd.array(v, ctx=contexts[0])
                  for k, v in copies(w0).items()}
    aux_params = {k: mx.nd.array(v, ctx=contexts[0])
                  for k, v in copies(aux0).items()}

    holder = {"outs": []}
    labels = [y for _, y in pool]

    def losses_of(outs):
        """Mean cross-entropy of each held softmax output, on the host."""
        res = []
        for i, out in enumerate(outs):
            p = np.asarray(out, np.float32)
            lab = labels[i].astype(np.int64)
            res.append(float(-np.mean(np.log(np.maximum(
                p[np.arange(len(lab)), lab], 1e-30)))))
        return res

    after_first, finish = program_readings(mod, opt, losses_of, w0,
                                           aux0, holder)

    def warm_cb(param):
        if len(holder["outs"]) < compared:
            holder["outs"].append(mod.get_outputs()[0]._data)
        if param.nbatch == 0:
            after_first()

    fit_kwargs = dict(eval_metric=mx.metric.create(traffic["eval_metric"]),
                      num_epoch=1, kvstore=traffic["kvstore"],
                      optimizer=o["name"], optimizer_params=opt)
    warm_iter = make_iter(pool, descs, count=warm)
    mod.fit(warm_iter, arg_params=arg_params, aux_params=aux_params,
            initializer=None, batch_end_callback=warm_cb, **fit_kwargs)
    del arg_params, aux_params
    mark("fit_warmup_dispatched")
    prog = finish()          # waits for the warm-up steps
    mark("warmup_done_and_read")
    reference_inputs = dict(
        params=w0, aux=aux0,
        batches=[(pool[i][0], pool[i][1]) for i in range(compared)],
        lr=o["learning_rate"], momentum=o["momentum"], wd=o["wd"])
    if harness.get("readings_only"):
        # for the tool that reads limits: the first steps, no window
        del mod, warm_iter, after_first, finish, holder
        gc.collect()
        return dict(program=prog, reference_inputs=reference_inputs,
                    device=dev)

    # ---- the measured window ------------------------------------------------
    seconds = float(args.seconds)
    trace_steps = int(traffic["trace_steps"]) if args.trace else 0
    trace_dir = os.path.join(ROOT, "benchmarks", ".out", "trace",
                             cell["name"])
    watcher = Watcher()
    cpu_marks, marks = [], []
    tr = {}

    def cb(param):
        with jax.profiler.TraceAnnotation("fit.batch_end"):
            out = mod.get_outputs()[0]._data
            watcher.put(out)
            marks.append(time.perf_counter())
            cpu_marks.append(time.thread_time())
            if trace_steps and param.nbatch + 1 == trace_steps:
                with jax.profiler.TraceAnnotation("drain"):
                    out.block_until_ready()
                tr["window"].__exit__(None, None, None)
                tr["t1"] = time.perf_counter()
                jax.profiler.stop_trace()
                tr["steps"] = trace_steps
                tr["stopped"] = time.perf_counter()

    counters0 = telemetry.counters()
    compiles0 = telemetry.span_count("jit_compile")
    compile_s0 = telemetry.span_seconds("jit_compile")
    cache0 = watch.counts()
    it = make_iter(pool, descs, seconds=seconds, offset=warm)
    if trace_steps:
        import shutil
        shutil.rmtree(trace_dir, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0     # host spans only where asked
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        tr["t0"] = time.perf_counter()
        tr["window"] = jax.profiler.TraceAnnotation("bench.window")
        tr["window"].__enter__()
    cpu0 = time.thread_time()
    mod.fit(it, batch_end_callback=cb, **fit_kwargs)
    t_first = it.first_request
    with jax.profiler.TraceAnnotation("drain"):
        done = watcher.close()
    t_end = done[-1] if done else time.perf_counter()
    if trace_steps and "stopped" not in tr:
        raise BenchFailure("the window ended before %d traced steps"
                           % trace_steps)
    steps = len(done)
    counters1 = telemetry.counters()
    compiles_in = telemetry.span_count("jit_compile") - compiles0
    cache1 = watch.counts()

    # ---- the path's own checks: a miss is a failed run ----------------------
    failed = []
    if mod._fused_fallback_reason is not None:
        failed.append("Module.fit left the fused step: %r"
                      % (mod._fused_fallback_reason,))
    disp = {k: v - counters0.get(k, 0) for k, v in counters1.items()
            if k.startswith("dispatch.") and v != counters0.get(k, 0)}
    if disp != {"dispatch.train_step": steps}:
        failed.append("expected one train_step dispatch per batch and "
                      "nothing else, got %r for %d batches" % (disp, steps))
    if compiles_in:
        failed.append("%d compilations inside the window" % compiles_in)
    if any(cache1[k] != cache0[k] for k in cache0):
        failed.append("the persistent cache was consulted inside the "
                      "window: %r -> %r" % (cache0, cache1))
    aot = {k: c["aot_fallback"] for k, c in telemetry.programs().items()
           if c.get("aot_fallback")}
    if aot:
        failed.append("programs fell back from AOT to plain jit: %r" % aot)
    if steps < 2:
        failed.append("only %d step(s) completed in the window" % steps)

    gaps = np.diff(np.asarray([t_first] + done)) if done else np.zeros(0)
    cpu_per_batch = np.diff(np.asarray([cpu0] + cpu_marks))
    window_s = t_end - t_first
    stats = {d.id: (d.memory_stats() or {}) for d in devices[:chips]}
    # a running program's temporaries live in the allocator's reserved
    # region, which ``peak_bytes_in_use`` leaves out: the chip's peak is
    # the two together (PERF.md, Findings, PR 24)
    peak = max((st.get("peak_bytes_in_use", 0)
                + st.get("peak_bytes_reserved", 0) for st in stats.values()),
               default=0)

    result = dict(
        steps=steps, batch=batch, chips=chips, window_s=window_s,
        setup_s=it.first_request - t_process, setup_marks_s=marks_s,
        step_gaps_s=gaps, cpu_per_batch_s=cpu_per_batch,
        dispatches=disp, compiles_in_window=compiles_in,
        compile_setup_s=compile_s0, cache=cache1, cache_dir=cache_dir,
        failed_checks=failed, memory_peak_bytes=int(peak),
        nodes=nodes, node_shapes=node_shapes, device=dev,
        trace=(dict(dir=trace_dir, t0=tr["t0"], t1=tr["t1"],
                    steps=tr["steps"]) if trace_steps else None),
        program=prog, max_ahead=_max_ahead(marks, done),
        memory_stats={str(k): {n: int(v) for n, v in st.items()
                               if isinstance(v, int)}
                      for k, st in stats.items()})

    # ---- free the program's state, then the reference -----------------------
    del mod, it, warm_iter, watcher, holder, after_first, finish
    gc.collect()
    result["reference_inputs"] = reference_inputs
    return result


def _max_ahead(marks, done):
    """How many steps the host had dispatched beyond those completed, at
    most, over the window."""
    if not marks or not done:
        return 0
    done = np.asarray(done)
    ahead = [i + 1 - int(np.searchsorted(done, t)) for i, t in
             enumerate(marks)]
    return int(max(ahead))
