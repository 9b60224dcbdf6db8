"""Device time per step in the ops of ``jit_train_step`` whose scope path
lies under ``backward`` or spells a transposed op (``transpose(jvp(
<node>))``): data gradients, weight gradients, the gradient casts."""
from benchmarks.harness import program_spans


def read(ctx):
    v = program_spans.view(ctx)
    return None if v is None else v.phase_ms("backward")
