"""Device time per step in the short-convolution mixers: the leaf ops under
their input projections, the gated convolutions and their output
projections (``*_conv_in``, ``*_shortconv``, ``*_conv_out``), forward,
recomputed and backward. Leaf ops only: a loop is not counted beside its
body."""
from benchmarks.harness import hybrid_trace


def read(ctx):
    v = hybrid_trace.view(ctx)
    return None if v is None else v.ms(("_conv_in", "_shortconv",
                                        "_conv_out"))
