"""Device time per step under the delta-rule recurrence's nodes
(``*_kda_core``: norms and decays, the chunks' blocks and their inverse, the
loop that carries the state, the output's products), forward, recomputed
and backward. Leaf ops only."""
from benchmarks.harness import hybrid_trace


def read(ctx):
    v = hybrid_trace.view(ctx)
    return None if v is None else v.ms(("_kda_core",))
