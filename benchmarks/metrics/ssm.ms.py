"""Device time per step in the Mamba-2 mixers' own ops: those under the
convolution's, the state-space recurrence's and the gated norm's nodes
(``*_mixer_conv``, ``*_mixer_ssd``, ``*_mixer_norm``), forward, recomputed
and backward; the projections around them are matrix products like any
other. Leaf ops only: a loop is not counted beside its body."""
from benchmarks.harness import hybrid_trace


def read(ctx):
    v = hybrid_trace.view(ctx)
    return None if v is None else v.ms(("_mixer_conv", "_mixer_ssd",
                                        "_mixer_norm"))
