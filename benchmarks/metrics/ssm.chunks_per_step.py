"""Chunks of the state-space recurrence computed a training step, over all
mixers (the program's ``ssm.chunks_run`` and ``ssm.steps`` counters, which
it counts on the host from each mixer's bound shapes and the fused steps
it ran): sequences x ceil(T / chunk) x mixers, 256 in
``nemotron3_nano.fit``. The shapes fix it: it says that the op ran in the
fused step; that it ran in its chunked form is read off the ``ssd/*``
scopes of the trace. A program without the counters reads nothing."""


def read(ctx):
    lm = ctx["run"].get("lm")
    if lm is None:
        return None
    from mxnet_tpu import telemetry
    c = telemetry.counters()
    if not c.get("ssm.steps") or "ssm.chunks_run" not in c:
        return None
    mixers = list(lm["model"]["layer_types"]).count("mamba")
    return c["ssm.chunks_run"] / c["ssm.steps"] * mixers
