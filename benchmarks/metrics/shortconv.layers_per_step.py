"""Short-convolution mixers run a training step (the program's
``shortconv.runs`` and ``shortconv.steps`` counters, which it counts on
the host from the bound symbol and the fused steps it ran): 5 in
``lfm2_moe.fit``. It says that every mixer ran in the fused step. A
program without the counters reads nothing."""


def read(ctx):
    if ctx["run"].get("lm") is None:
        return None
    from mxnet_tpu import telemetry
    c = telemetry.counters()
    if not c.get("shortconv.steps") or "shortconv.runs" not in c:
        return None
    return c["shortconv.runs"] / c["shortconv.steps"]
