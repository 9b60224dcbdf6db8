"""XLA programs built before the window that JAX's persistent cache did not
serve (``xla_build`` events): 0 in a warm run, the programs compiled in a
cold one."""
from benchmarks.harness import set_up


def read(ctx):
    builds = set_up.builds_before_window(ctx)
    return None if builds is None else sum(not b["cache_hit"]
                                           for b in builds)
