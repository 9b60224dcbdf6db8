"""Device time per step in the ops of ``jit_train_step`` whose scope path
lies under ``optimizer``. A fusion carries its root instruction's path, so
an update fused into a gradient's fusion counts as backward."""
from benchmarks.harness import program_spans


def read(ctx):
    v = program_spans.view(ctx)
    return None if v is None else v.phase_ms("optimizer")
