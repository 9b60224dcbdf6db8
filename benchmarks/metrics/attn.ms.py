"""Device time per step under the attention nodes' scopes
(``*_attn_core``: the kernels with the transposes around them), forward,
recomputed and backward."""
from benchmarks.harness import lm_trace


def read(ctx):
    v = lm_trace.view(ctx)
    return None if v is None else v.ms(("_attn_core",))
