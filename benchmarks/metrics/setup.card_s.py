"""Seconds in the program's ``jit_card`` spans over the process: the cost
and memory analyses behind each instrumented program's card."""


def read(ctx):
    from mxnet_tpu import telemetry
    if not telemetry.span_count("jit_card"):
        return None
    return telemetry.span_seconds("jit_card")
