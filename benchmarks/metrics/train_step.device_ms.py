"""Device time of the step program per step: the runs of the XLA module
that takes most of the traced window, median, first device."""
from benchmarks.harness import trace_reduce as tr


def read(ctx):
    t = ctx["trace"]
    if t is None:
        return None
    found = tr.step_module(t.modules, t.lo, t.hi)
    if found is None or not found[1]:
        return None
    return 1e3 * tr.median(found[1])
