"""Device time per step in the ops of XLA category "convolution fusion"
that ``backward.ms`` counts: data and weight gradients, with what XLA fused
into them. The least time (two thirds of ``flops.conv_least_seconds``) is
printed beside it on standard error, as is any convolution time that is in
neither this nor ``conv_fwd.ms``."""
from benchmarks.harness import program_spans as ps


def read(ctx):
    v = ps.view(ctx)
    ms = None if v is None else v.phase_ms("backward", conv=True)
    if ms is not None and ctx["peaks"] is not None:
        ps.say("conv_bwd.ms %.3f against a least time of %.3f; %.3f ms of "
               "convolutions in neither direction"
               % (ms, ps.conv_least_ms(ctx, 2),
                  v.phase_ms("optimizer", conv=True)
                  + v.phase_ms("other", conv=True)))
    return ms
