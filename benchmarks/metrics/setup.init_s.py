"""Seconds in the program's ``init_params`` + ``init_optimizer`` spans
over the process: every leaf copied in, masters and momentum made."""


def read(ctx):
    from mxnet_tpu import telemetry
    names = ("init_params", "init_optimizer")
    if not any(telemetry.span_count(n) for n in names):
        return None
    return sum(telemetry.span_seconds(n) for n in names)
