"""XLA programs built before the window that no instrumented build made
(``xla_build`` events): compile sites outside the instrumented one, bodies
jitted by themselves, eager calls, the benchmark's weights, pool and
readings."""
from benchmarks.harness import set_up


def read(ctx):
    builds = set_up.builds_before_window(ctx)
    return None if builds is None else len(set_up.others(builds))
