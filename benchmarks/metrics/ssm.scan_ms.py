"""Device time per step under the state-space recurrence's nodes
(``*_mixer_ssd``: decays, the chunks' products, the pass of the states),
forward, recomputed and backward. Leaf ops only."""
from benchmarks.harness import hybrid_trace


def read(ctx):
    v = hybrid_trace.view(ctx)
    return None if v is None else v.ms(("_mixer_ssd",))
