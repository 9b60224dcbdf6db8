"""Device time per step in the expert layers: the ops under the routed
layer's (``*_moe``) and the shared expert's (``*_shared_*``) nodes, and
the routed layer's grouped products where they carry no scope path."""
from benchmarks.harness import lm_trace


def read(ctx):
    v = lm_trace.view(ctx)
    nodes = None if v is None else v.ms(("_moe", "_shared_"))
    if nodes is None:
        return None
    every, under = v.grouped_ms()
    return nodes + ((every - under) if every else 0.0)
