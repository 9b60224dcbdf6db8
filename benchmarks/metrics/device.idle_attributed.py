"""Share of the first device's idle seconds in the window that lie under
one of the program's spans; the split by span name (each gap to the
innermost span) goes to standard error."""
from benchmarks.harness import program_spans as ps


def read(ctx):
    v = ps.view(ctx)
    if v is None or not v.spans:
        return None
    t = v.trace
    found = ps.idle_under_spans(t.ops, v.spans, t.lo, t.hi)
    if found is None or not found[0]:
        return None
    idle, under, split = found
    ps.say("idle %.6f s, under the program's spans %.6f s: %s"
           % (idle, under, ", ".join(
               "%s %.6f" % kv for kv in sorted(split.items(),
                                               key=lambda kv: -kv[1]))))
    return 100.0 * under / idle
