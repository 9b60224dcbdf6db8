"""Median over the traced steps of the ``feed`` span: the batch handed to
the device (``device_put``) and the state inputs gathered. The program's
own annotation, read from the profiler's trace inside ``bench.window``."""
from benchmarks.harness import program_spans as ps


def read(ctx):
    v = ps.view(ctx)
    return None if v is None else v.step_ms((ps.FEED,))
