"""Device time per step in the routed layer's combine kernel
(``moe_combine``, ``mxnet_tpu/pallas/moe_combine.py``): a token's held
rows added up, forward and in the dispatch's transpose, two runs a routed
layer. Found by the kernel's name in the instruction or the scope path,
under whatever node (XLA may give a custom call no scope path on the
chip); ``None`` where no such kernel ran, as in a program without it."""
from benchmarks.harness import lm_trace


def read(ctx):
    v = lm_trace.view(ctx)
    return None if v is None else v.ms(("",), "moe_combine")
