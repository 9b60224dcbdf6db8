"""Host time no span names: median over the traced steps of (start of the
next ``fit_batch`` less start of this one) less the time under any other
span of the program in between, nested spans counted once."""
from benchmarks.harness import program_spans as ps


def read(ctx):
    v = ps.view(ctx)
    if v is None:
        return None
    t = v.trace
    return ps.median_ms(ps.unspanned_seconds(v.spans, t.lo, t.hi))
