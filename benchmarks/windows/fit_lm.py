"""The ``fit_lm`` window: ``Module.fit`` on a language model, driven from a
seeded pool of token batches.

As ``windows/fit.py`` (whose feed, watcher and path checks it loads and
uses as they are): set-up builds ONE Module, feeds it the benchmark's own
weights, drives it through its first steps with ``fit`` (the steps the
reference follows) and hands the same Module to the timed ``fit`` call.
What differs is what a language model needs: ids and next ids of shape
(sequences, T) in place of images and classes, the model's own
initialisation, Adam's state in place of momentum, a held output that is
the loss of every position, and the routed-expert layer's counters.
Nothing of the model's size or name is decided here.
"""
import gc
import os
import time

import numpy as np

from benchmarks.harness import inputs
from benchmarks.harness.files import ROOT, load_file

fit = load_file("benchmarks/windows/fit.py", "bench_window_fit")
BenchFailure = fit.BenchFailure

#: the keys of a configuration's file that are the symbol's arguments
MODEL_KEYS = (
    "hidden_size", "num_attention_heads", "num_key_value_heads", "head_dim",
    "layer_types", "num_hidden_layers", "sliding_window", "rope_theta",
    "num_dense_layers", "intermediate_size", "num_experts_per_tok",
    "moe_intermediate_size", "num_shared_experts", "score_func",
    "route_norm", "route_scale", "load_balance_coeff", "rms_norm_eps",
    "vocab_size", "mup_enabled")


def model_config(cfg, rehearse):
    """The model as the symbol and the reference take it: the file's keys,
    with ``num_experts`` the router's width and ``experts_held`` the
    ``(first, count)`` this chip holds."""
    c = {k: cfg[k] for k in MODEL_KEYS}
    c["num_experts"] = cfg["router_experts"]
    c["experts_held"] = [0, cfg["num_experts"]]
    if rehearse:
        c.update(cfg["rehearse"]["model"])
    return c


def build_symbol(cfg, model, rehearse):
    s = cfg["symbol"]
    kwargs = dict(s["kwargs"], **model)
    if rehearse:
        kwargs.update(cfg["rehearse"]["symbol_kwargs"])
    fn = getattr(load_file(s["file"], "bench_symbol"), s["function"])
    return fn(**kwargs)


def shapes_of(sym, cfg, dtypes, batch_shape):
    """By name: each parameter's ``(shape, dtype)`` and each auxiliary
    state's shape as the program's inference places them, the symbol's
    nodes and every node's output shape."""
    import json
    s = cfg["symbol"]
    known = {s["data_name"]: tuple(batch_shape),
             s["label_name"]: tuple(batch_shape)}
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


def make_weights(seed, leaves, std):
    """``{name: array}`` in one jitted call: norms' scales (``*_gamma``) 1,
    everything else normal with ``std``, drawn in float32 and rounded to
    the leaf's type."""
    import jax
    import jax.numpy as jnp
    names = sorted(leaves)

    def make(key):
        out = {}
        for k, name in zip(jax.random.split(key, len(names)), names):
            shape, dtype = leaves[name]
            out[name] = jnp.ones(shape, dtype) if name.endswith("_gamma") \
                else (jax.random.normal(k, shape, jnp.float32)
                      * std).astype(dtype)
        return out

    return jax.jit(make)(jax.random.fold_in(inputs.root_key(seed), 1))


def make_pool(seed, n, sequences, seq_len, vocab):
    """``n`` host batches ``(ids, next ids)``: every sequence is one
    document of ``seq_len + 1`` ids uniform over the vocabulary."""
    import jax
    import jax.numpy as jnp
    key = jax.random.fold_in(inputs.root_key(seed), 2)
    ids = np.asarray(jax.jit(lambda k: jax.random.randint(
        k, (n, sequences, seq_len + 1), 0, vocab, jnp.int32))(key))
    return [(np.ascontiguousarray(b[:, :-1]),
             np.ascontiguousarray(b[:, 1:]).astype(np.float32)) for b in ids]


def adam_state(mod):
    """``{name: (first moment, float32 master)}`` as raw device arrays:
    multi-precision leaves hold ((mean, var), master); leaves that are
    float32 already (the norms' scales) hold (mean, var) and are their own
    master."""
    states = fit.effective_updater(mod).states
    out = {}
    for i, name in enumerate(mod._param_names):
        st = states.get(i)
        if st is None:
            continue
        moments, w32 = st if isinstance(st[0], tuple) \
            else (st, mod._exec.arg_dict[name])
        out[name] = (moments[0]._data, w32._data)
    return out


def program_readings(mod, opt, w0_host, aux0_host, holder):
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
    def diff_norm(a, b):
        return jnp.sqrt(jnp.sum(jnp.square(a.astype(f32) - b.astype(f32))))

    def after_first_step():
        # Adam's first moment after one step from zero is (1 - beta1) *
        # rescale_grad * grad (wd is 0): the first gradient as the
        # optimizer got it
        holder["mean_norms"] = norms({k: v[0]
                                      for k, v in adam_state(mod).items()})

    def finish():
        # the starting point comes back from the host a leaf at a time:
        # a second copy of the weights does not sit on the device through
        # the steps
        change = {k: float(diff_norm(v[1], w0_host[k]))
                  for k, v in adam_state(mod).items()}
        aux_change = {k: float(diff_norm(a._data, aux0_host[k]))
                      for k, a in mod._exec.aux_dict.items()}
        return dict(
            losses=[float(np.mean(np.asarray(o, np.float32)))
                    for o in holder["outs"]],
            grad_norms={k: float(v) / (1.0 - opt["beta1"])
                        for k, v in holder["mean_norms"].items()},
            change_norms=change, aux_change_norms=aux_change)

    return after_first_step, finish


def run(cell, cfg, traffic, args, harness):
    """Set up, measure, check. Returns the pieces ``run.py`` prints."""
    t_process = harness["t_process"]
    rehearse = args.rehearse
    marks_s = {}

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
    if chips != 1 or int(traffic["chips"]) != 1:
        raise BenchFailure("the fit_lm window drives one chip")

    import mxnet_tpu as mx
    from mxnet_tpu import jax_cache, telemetry

    mark("import_mxnet_tpu")
    os.environ.pop("MXNET_COMPILE_CACHE", None)
    cache_dir = jax_cache.place()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    watch = jax_cache.CacheWatch()

    size = traffic["rehearse"] if rehearse else traffic
    sequences, seq_len = int(size["sequences_per_step"]), int(size["seq_len"])
    tokens = sequences * seq_len
    if not rehearse and tokens != int(traffic["batch_per_chip"]):
        raise BenchFailure("batch_per_chip is the tokens of a step")
    model = model_config(cfg, rehearse)
    sym = build_symbol(cfg, model, rehearse)
    s = cfg["symbol"]
    dtypes = {"data": cfg["dtypes"]["data"], "label": cfg["dtypes"]["label"]}
    known, pshapes, ashapes, nodes, node_shapes = shapes_of(
        sym, cfg, dtypes, (sequences, seq_len))

    w0 = make_weights(args.seed, pshapes, cfg["init_std"])
    w0_host = jax.device_get(w0)
    aux0_host = {k: np.zeros(v, np.float32) for k, v in ashapes.items()}
    n_pool = int(traffic["pool_batches"])
    warm = int(traffic["warmup_steps"])
    compared = int(traffic["compared_steps"])
    if compared > warm or warm > n_pool:
        raise BenchFailure("compared_steps <= warmup_steps <= pool_batches")
    pool = make_pool(args.seed, n_pool, sequences, seq_len,
                     model["vocab_size"])
    mark("weights_and_pool")

    context = mx.cpu(0) if dev.platform == "cpu" else mx.tpu(0)
    mod = mx.mod.Module(sym, data_names=(s["data_name"],),
                        label_names=(s["label_name"],), context=context)
    o = dict(cfg["optimizer"])
    if rehearse:
        o.update(cfg["rehearse"].get("optimizer", {}))
    opt = {k: o[k] for k in ("learning_rate", "beta1", "beta2", "epsilon",
                             "wd", "multi_precision")}
    opt["rescale_grad"] = 1.0 / tokens
    descs = ((s["data_name"], known[s["data_name"]],
              np.dtype(dtypes["data"])),
             (s["label_name"], known[s["label_name"]]))
    # the Module gets the weights themselves: wrapping and ``copyto`` on
    # one device alias the buffer, the fused step donates it, and the
    # starting point the readings need is on the host already
    arg_params = {k: mx.nd.array(v, ctx=context) for k, v in w0.items()}
    aux_params = {k: mx.nd.array(v, ctx=context)
                  for k, v in aux0_host.items()}
    del w0

    holder = {"outs": []}
    after_first, finish = program_readings(mod, opt, w0_host, aux0_host,
                                           holder)

    def warm_cb(param):
        if len(holder["outs"]) < compared:
            holder["outs"].append(mod.get_outputs()[0]._data)
        if param.nbatch == 0:
            after_first()

    fit_kwargs = dict(eval_metric=mx.metric.create(traffic["eval_metric"]),
                      num_epoch=1, kvstore=traffic["kvstore"],
                      optimizer=o["name"], optimizer_params=opt)
    warm_iter = fit.make_iter(pool, descs, count=warm)
    mod.fit(warm_iter, arg_params=arg_params, aux_params=aux_params,
            initializer=None, batch_end_callback=warm_cb, **fit_kwargs)
    del arg_params, aux_params
    mark("fit_warmup_dispatched")
    prog = finish()          # waits for the warm-up steps
    # the routed-expert layers' counters of the compared steps: fit has
    # published them at its epoch's end
    moe = {k: v for k, v in telemetry.counters().items()
           if k.startswith("moe.")}
    mark("warmup_done_and_read")
    reference_inputs = dict(
        params=w0_host, aux=aux0_host,
        batches=[pool[i] for i in range(compared)],
        lr=o["learning_rate"], momentum=0.0, wd=o["wd"])
    lm = dict(model=model, tokens=tokens, seq_len=seq_len, moe=moe)
    if harness.get("readings_only"):
        del mod, warm_iter, after_first, finish, holder
        gc.collect()
        return dict(program=prog, reference_inputs=reference_inputs,
                    device=dev, lm=lm)

    # ---- the measured window ------------------------------------------------
    seconds = float(args.seconds)
    trace_steps = int(traffic["trace_steps"]) if args.trace else 0
    trace_dir = os.path.join(ROOT, "benchmarks", ".out", "trace",
                             cell["name"])
    watcher = fit.Watcher()
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
    it = fit.make_iter(pool, descs, seconds=seconds, offset=warm)
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
    stats = dev.memory_stats() or {}
    # a running program's temporaries live in the allocator's reserved
    # region, which ``peak_bytes_in_use`` leaves out (PERF.md, PR 24)
    peak = stats.get("peak_bytes_in_use", 0) \
        + stats.get("peak_bytes_reserved", 0)

    result = dict(
        steps=steps, batch=tokens, chips=chips, window_s=t_end - t_first,
        setup_s=it.first_request - t_process, setup_marks_s=marks_s,
        step_gaps_s=gaps, cpu_per_batch_s=cpu_per_batch,
        dispatches=disp, compiles_in_window=compiles_in,
        compile_setup_s=compile_s0, cache=cache1, cache_dir=cache_dir,
        failed_checks=failed, memory_peak_bytes=int(peak),
        nodes=nodes, node_shapes=node_shapes, device=dev, lm=lm,
        trace=(dict(dir=trace_dir, t0=tr["t0"], t1=tr["t1"],
                    steps=tr["steps"]) if trace_steps else None),
        program=prog, max_ahead=fit._max_ahead(marks, done),
        memory_stats={str(dev.id): {n: int(v) for n, v in stats.items()
                                    if isinstance(v, int)}})

    # ---- free the program's state, then the reference -----------------------
    del mod, it, warm_iter, watcher, holder, after_first, finish
    gc.collect()
    result["reference_inputs"] = reference_inputs
    return result
