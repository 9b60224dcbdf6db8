"""Seconds in the program's ``jit_trace`` spans over the process: tracing
the instrumented programs alone. ``None`` where no ``jit_lower`` span was
recorded: there ``jit_trace`` holds the lowering too."""


def read(ctx):
    from mxnet_tpu import telemetry
    if not telemetry.span_count("jit_lower"):
        return None
    return telemetry.span_seconds("jit_trace")
