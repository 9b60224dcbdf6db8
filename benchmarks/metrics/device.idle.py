"""1 - (union of intervals with an op running) / traced window, on the
device that was busy least."""
from benchmarks.harness import trace_reduce as tr


def read(ctx):
    t = ctx["trace"]
    if t is None:
        return None
    idle = tr.idle_share(t.ops, t.lo, t.hi)
    return None if idle is None else 100.0 * idle
