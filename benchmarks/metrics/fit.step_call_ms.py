"""Median over the traced steps of the ``step`` span: the enqueue of the
fused step (flatten, signature look-up, the executable's call). In the
traced steps the host is never 32 ahead, so this is the host's own work
and no wait on the runtime's queue."""
from benchmarks.harness import program_spans as ps


def read(ctx):
    v = ps.view(ctx)
    return None if v is None else v.step_ms((ps.CALL,))
