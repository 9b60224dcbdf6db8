"""Seconds in the program's ``jit_lower`` spans over the process: lowering
the instrumented programs' traces to StableHLO."""


def read(ctx):
    from mxnet_tpu import telemetry
    if not telemetry.span_count("jit_lower"):
        return None
    return telemetry.span_seconds("jit_lower")
