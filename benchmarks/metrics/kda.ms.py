"""Device time per step in the Kimi Delta Attention mixers' own ops: those
under the three convolutions', the recurrence's and the gated norm's nodes
(``*_kda_conv_q`` / ``_k`` / ``_v``, ``*_kda_core``, ``*_kda_norm``),
forward, recomputed and backward; the projections around them are matrix
products like any other. Leaf ops only: a loop is not counted beside its
body."""
from benchmarks.harness import hybrid_trace


def read(ctx):
    v = hybrid_trace.view(ctx)
    return None if v is None else v.ms(("_kda_conv", "_kda_core",
                                        "_kda_norm"))
