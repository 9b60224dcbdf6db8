"""Device time per step in the attention forward kernel
(``flash_attention_fwd`` under the ``*_attn_core`` nodes): one run a layer
where the layer's checkpoint segment keeps the kernel's output and
log-sum-exp, two where the segment makes them again."""
from benchmarks.harness import lm_trace


def read(ctx):
    v = lm_trace.view(ctx)
    return None if v is None else v.ms(("_attn_core",),
                                       "flash_attention_fwd")
