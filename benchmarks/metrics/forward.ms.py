"""Device time per step in the ops of ``jit_train_step`` whose scope path
lies under ``forward`` and names no transposed op: the forward pass, its
convolutions and its elementwise passes alike."""
from benchmarks.harness import program_spans


def read(ctx):
    v = program_spans.view(ctx)
    return None if v is None else v.phase_ms("forward")
