"""Device time per step under the routed layer's node that is not in its
grouped products: router, top-k, sort, gathers, the gate between the
products (an elementwise pass over the sorted buffers), combine."""
from benchmarks.harness import lm_trace


def read(ctx):
    v = lm_trace.view(ctx)
    if v is None:
        return None
    whole = v.ms(("_moe",))
    return None if whole is None else whole - (v.grouped_ms()[1] or 0.0)
