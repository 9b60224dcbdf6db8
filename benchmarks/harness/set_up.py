"""What the program logs of its own set-up, for the ``setup.*`` readers.

Two sources, both in the program's telemetry: the ``xla_build`` events
(one for every XLA program the process built, compiled or loaded from
JAX's persistent cache, with its function name, seconds, whether the
cache served it and whether an instrumented build made it), and the span
ring, whose ``fit_setup`` and ``jit_build`` spans have a self time.

A program that logs neither (one older than these spans and events) gives
``None``, and so does a state whose ring has lost what a reader needs:
a number is read whole or not at all.
"""
import collections
import sys

from benchmarks.harness import trace_reduce as tr

#: the set-up spans whose own time ``self_seconds`` reads
OUTER = ("fit_setup", "jit_build")


def builds_before_window(ctx):
    """The data of each ``xla_build`` event logged before the window
    opened (``ctx["run"]["trace"]["t0"]``, a ``time.perf_counter()``
    reading of this process): the reference builds its programs after the
    window and is left out. ``None`` where the run was not traced, the
    program logs no such event, or some have left the event ring."""
    from mxnet_tpu import telemetry
    trace = ctx["run"].get("trace")
    if trace is None:
        return None
    logged = telemetry.counters().get("xla.programs", 0)
    builds = [e for e in telemetry.events() if e["kind"] == "xla_build"]
    if not logged or len(builds) != logged:
        return None
    t0 = telemetry.epoch_s(trace["t0"])
    return [e["data"] for e in builds if e["ts"] < t0]


def _interval(span):
    return span["ts"], span["ts"] + span["dur_ms"] / 1e3


def self_seconds():
    """Seconds under the ``fit_setup`` and ``jit_build`` spans over the
    process less those under the program spans nested in them, each
    second counted once; ``None`` where there is no such span or the ring
    has dropped one of them or a span that may lie inside one."""
    from mxnet_tpu import telemetry
    spans = telemetry.recent_spans()
    outer = [s for s in spans if s["name"] in OUTER]
    if not outer or any(
            telemetry.span_count(n) != sum(s["name"] == n for s in outer)
            for n in OUTER):
        return None
    recorded = sum(telemetry.span_count(n) for n in telemetry.span_stats())
    if recorded > len(spans):
        # the ring dropped its oldest: each of them ended before the
        # oldest kept one did, so none lies inside a later outer span
        if min(s["ts"] for s in outer) < _interval(spans[0])[1]:
            return None
    inner = []
    for s in spans:
        if s["name"] in OUTER:
            continue
        a, b = _interval(s)
        if any(o["tid"] == s["tid"] and _interval(o)[0] <= a
               and b <= _interval(o)[1] for o in outer):
            inner.append((a, b))
    return tr.length(tr.subtract(tr.union([_interval(s) for s in outer]),
                                 tr.union(inner)))


def others(builds):
    """The builds no instrumented build made: the compile sites outside
    ``executor._InstrumentedProgram``, bodies jitted by themselves, eager
    calls, and the benchmark's own weights, pool and readings."""
    return [b for b in builds if not b["instrumented"]]


def say_largest(builds, n=10):
    """The ``n`` function names with the most build seconds, with their
    count, to standard error."""
    secs = collections.Counter()
    count = collections.Counter()
    for b in builds:
        secs[b["fn"]] += b["s"]
        count[b["fn"]] += 1
    print("set_up: %d programs built outside an instrumented build, "
          "%.6f s; largest: %s" % (
              len(builds), sum(secs.values()),
              ", ".join("%s x%d %.6f" % (fn, count[fn], s)
                        for fn, s in secs.most_common(n))),
          file=sys.stderr)
