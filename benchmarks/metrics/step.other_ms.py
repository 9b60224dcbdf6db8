"""The rest of the step module's op time per step: the metric, and every
op whose metadata has no scope path or one that starts elsewhere (copies,
slices, formatting). The share of the module's time that had a path at all
goes to standard error."""
from benchmarks.harness import program_spans


def read(ctx):
    v = program_spans.view(ctx)
    ms = None if v is None else v.phase_ms("other")
    if ms is not None:
        program_spans.say("%.4f of jit_train_step's op time carries a "
                          "scope path" % v.scopes()[2])
    return ms
