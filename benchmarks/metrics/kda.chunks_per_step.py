"""Chunks of the delta-rule recurrence computed a training step, over all
KDA layers (the program's ``kda.chunks_run`` and ``kda.steps`` counters,
which it counts on the host from each layer's bound shapes and the fused
steps it ran): sequences x ceil(T / chunk) x layers, 512 in
``kimi_linear.fit``. The shapes fix it: it says that the op ran in the fused
step; that it ran in its chunked form is read off the ``kda/*`` scopes of
the trace. A program without the counters reads nothing."""


def read(ctx):
    lm = ctx["run"].get("lm")
    if lm is None:
        return None
    from mxnet_tpu import telemetry
    c = telemetry.counters()
    if not c.get("kda.steps") or "kda.chunks_run" not in c:
        return None
    model = lm["model"]
    kept = list(model["layer_types"])[:model["num_hidden_layers"]]
    return c["kda.chunks_run"] / c["kda.steps"] * kept.count("kda")
