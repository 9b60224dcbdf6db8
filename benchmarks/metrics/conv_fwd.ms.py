"""Device time per step in the ops of XLA category "convolution fusion"
that ``forward.ms`` counts. Milliseconds and no share of a roofline: the
least time (a third of ``flops.conv_least_seconds``, which counts HBM bytes
an implementation may not need) is printed beside it on standard error."""
from benchmarks.harness import program_spans as ps


def read(ctx):
    v = ps.view(ctx)
    ms = None if v is None else v.phase_ms("forward", conv=True)
    if ms is not None and ctx["peaks"] is not None:
        ps.say("conv_fwd.ms %.3f against a least time of %.3f"
               % (ms, ps.conv_least_ms(ctx, 1)))
    return ms
