"""Seconds in the program's ``bind`` spans over the process (the window
binds nothing): symbol to executor, shapes, types, buffers."""


def read(ctx):
    from mxnet_tpu import telemetry
    if not telemetry.span_count("bind"):
        return None
    return telemetry.span_seconds("bind")
