"""Self time of the program's ``fit_setup`` and ``jit_build`` spans over the
process: their seconds less those of the program spans nested in them, each
counted once (``fit``'s preamble beside bind and initialisation; a build's
signature, keys and bookkeeping beside its phases)."""
from benchmarks.harness import set_up


def read(ctx):
    return set_up.self_seconds()
