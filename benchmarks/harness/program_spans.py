"""What the program itself wrote into a profiler trace (ISSUE 25): its
telemetry spans, which are annotations on ``/host:CPU`` on the device's
clock, and the scope path (``jax.named_scope``) of each device op of the
step program.

Two halves, as in ``trace_reduce``: ``load_host`` / ``scope_paths`` read
the ``.xplane.pb``; everything else works on plain tuples. A host span is
``(name, start_s, duration_s, stats)`` with the annotation's keyword
arguments (``nbatch``, ``step_num``, ...) as ``stats``; a device op is
``trace_reduce``'s ``(device, name, category, start_s, duration_s)``.

A program that writes no such annotation or scope (the parent of the PR
that added them, a CPU rehearsal for the scopes) gives empty lists, and
every reader built on this returns ``None``.
"""
import bisect
import collections
import sys

from benchmarks.harness import trace_reduce as tr

#: the stat of a device op's metadata that carries its scope path on the
#: chip, e.g. ``jit(train_step)/forward/jvp(stage1_unit1_conv1)/
#: conv_general_dilated:`` (looked at by hand, PR 25)
SCOPE_STAT = "tf_op"
STEP_MODULE = "jit_train_step"
STEP_SPAN = "fit_batch"
#: the leaves of one fit step, in the order they run
FEED, PREP, CALL, INSTALL = "feed", "step_prep", "step", "step_install"
PHASES = ("forward", "backward", "optimizer", "other")


def program_span_names():
    """Every span name the program declares (``mxnet_tpu.telemetry``)."""
    from mxnet_tpu import telemetry
    names = set()
    for group in ("FIT_PHASE_SPANS", "COMPILE_SPANS", "SETUP_SPANS"):
        names.update(getattr(telemetry, group, ()))
    return names


# -- reading -----------------------------------------------------------------

def load_host(path, window_name="bench.window"):
    """The program's spans on the thread that ran the window: the events of
    the ``/host:`` line that holds ``window_name`` whose name the program
    declares, sorted by start."""
    from jax.profiler import ProfileData
    wanted = program_span_names()
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            events = list(line.events)
            if not any(ev.name == window_name for ev in events):
                continue
            return sorted(
                ((ev.name, ev.start_ns * 1e-9, ev.duration_ns * 1e-9,
                  dict(ev.stats)) for ev in events if ev.name in wanted),
                key=lambda s: s[1])
    return []


def scope_paths(path):
    """``{device plane name: {op name: scope path}}``."""
    from benchmarks.harness import xplane_meta
    return xplane_meta.categories(path, SCOPE_STAT)


# -- scopes ------------------------------------------------------------------

def phase_of(path):
    """Which phase of the step an op's scope path names. ``forward`` under
    the ``forward`` scope; ``backward`` under ``backward`` or wherever JAX's
    name stack spells a transposed op (``transpose(jvp(forward/x))``);
    ``optimizer``; and ``other`` for the metric, an empty path, or one that
    starts anywhere else."""
    if not path:
        return "other"
    parts = path.rstrip(":").split("/")
    while parts and parts[0].startswith(("jit(", "pjit(")):
        parts = parts[1:]       # jit(train_step)/jit(main)/...
    if "transpose(" in path:
        return "backward"
    head = parts[0] if parts else ""
    return head if head in ("forward", "backward", "optimizer") else "other"


def node_of(path):
    """The symbol node a scope path names (``stage1_unit1_conv1`` of
    ``.../forward/jvp(stage1_unit1_conv1)/conv_general_dilated:``), or
    ``None``."""
    for part in filter(None, (path or "").rstrip(":").split("/")):
        inner = part
        while "(" in inner and inner.endswith(")"):
            inner = inner[inner.index("(") + 1:-1]
        if part in ("forward", "backward", "optimizer", "metric") \
                or inner in ("forward", "backward") \
                or part.startswith(("jit(", "pjit(")):
            continue
        return inner
    return None


def step_ops(ops, modules, lo, hi, module=STEP_MODULE):
    """The ops that start inside ``[lo, hi]`` and inside a run of the
    module whose name starts with ``module``, on that run's device."""
    runs = collections.defaultdict(list)
    for dev, name, s, d in modules:
        if name.startswith(module):
            runs[dev].append((s, s + d))
    for iv in runs.values():
        iv.sort()
    out = []
    for op in ops:
        dev, s = op[0], op[3]
        if not lo <= s <= hi:
            continue
        iv = runs.get(dev)
        if not iv:
            continue
        i = bisect.bisect_right(iv, (s, float("inf"))) - 1
        if i >= 0 and iv[i][0] <= s < iv[i][1]:
            out.append(op)
    return out


def seconds_by_phase(ops, paths):
    """Device seconds of ``ops`` by phase, all ops and the convolutions
    among them, mean over devices: ``({phase: s}, {phase: s}, share of the
    time whose op had a scope path at all)``. ``paths`` maps a device to
    ``{op name: scope path}``."""
    total = dict.fromkeys(PHASES, 0.0)
    conv = dict.fromkeys(PHASES, 0.0)
    scoped = 0.0
    for dev, name, cat, _, d in ops:
        path = paths.get(dev, {}).get(name)
        phase = phase_of(path)
        total[phase] += d
        if tr.is_conv(cat):
            conv[phase] += d
        if path:
            scoped += d
    n = max(len(tr.devices_of(ops)), 1)
    whole = sum(total.values())
    return ({k: v / n for k, v in total.items()},
            {k: v / n for k, v in conv.items()},
            scoped / whole if whole else None)


def seconds_by_node(ops, paths, conv_only=True):
    """``{(node, phase): seconds}`` summed over devices' mean, for the
    per-stage and per-direction table of PERF.md section 5."""
    out = collections.Counter()
    n = max(len(tr.devices_of(ops)), 1)
    for dev, name, cat, _, d in ops:
        if conv_only and not tr.is_conv(cat):
            continue
        path = paths.get(dev, {}).get(name)
        out[(node_of(path), phase_of(path))] += d / n
    return out


# -- host spans --------------------------------------------------------------

def _step_spans(spans, lo, hi):
    return [s for s in spans if s[0] == STEP_SPAN
            and lo <= s[1] and s[1] + s[2] <= hi]


def steps_of(spans, lo, hi):
    """The ``fit_batch`` spans inside ``[lo, hi]``, each with the program's
    spans that lie inside it: ``[(step, [inner])]``."""
    out = []
    for st in _step_spans(spans, lo, hi):
        a, b = st[1], st[1] + st[2]
        out.append((st, [s for s in spans if s is not st
                         and a <= s[1] and s[1] + s[2] <= b]))
    return out


def per_step_seconds(steps, names):
    """For each step the seconds under its inner spans named in ``names``;
    steps that hold none of them are left out."""
    out = []
    for _, inner in steps:
        hit = [s[2] for s in inner if s[0] in names]
        if hit:
            out.append(sum(hit))
    return out


def unspanned_seconds(spans, lo, hi):
    """For each step but the last: the time from its start to the next
    step's start that lies under none of the program's spans but the step
    annotation itself (which covers all of it), nested spans counted
    once."""
    steps = _step_spans(spans, lo, hi)
    named = tr.union([(s[1], s[1] + s[2]) for s in spans
                      if s[0] != STEP_SPAN])
    out = []
    for this, nxt in zip(steps, steps[1:]):
        gap = [(this[1], nxt[1])]
        out.append(tr.length(tr.subtract(gap, named)))
    return out


def median_ms(values):
    return 1e3 * tr.median(values) if values else None


def idle_under_spans(ops, spans, lo, hi):
    """``(idle seconds of the first device in [lo, hi], those of them under
    any of the program's spans, {span name: seconds})``. The split goes to
    the innermost span: the step annotation gets only what no other span
    covers."""
    devs = tr.devices_of(ops)
    if not devs or hi <= lo:
        return None
    busy = tr.clip(tr.union([(s, s + d) for dev, _, _, s, d in ops
                             if dev == devs[0]]), lo, hi)
    idle = tr.subtract([(lo, hi)], busy)
    inner = tr.union([(s[1], s[1] + s[2]) for s in spans
                      if s[0] != STEP_SPAN])
    outer = tr.union([(s[1], s[1] + s[2]) for s in spans])
    split = collections.Counter()
    for name in {s[0] for s in spans if s[0] != STEP_SPAN}:
        own = tr.union([(s[1], s[1] + s[2]) for s in spans if s[0] == name])
        split[name] = tr.length(idle) - tr.length(tr.subtract(idle, own))
    under_inner = tr.length(idle) - tr.length(tr.subtract(idle, inner))
    under_any = tr.length(idle) - tr.length(tr.subtract(idle, outer))
    split[STEP_SPAN + " alone"] = under_any - under_inner
    return tr.length(idle), under_any, {k: v for k, v in split.items() if v}


# -- one view a traced run ---------------------------------------------------

class ProgramView:
    """The program's spans and scopes of one ``TraceView``. The host spans
    are read at once; the scope paths (a second pass over the file) only
    when a reader of the device's phases asks."""

    def __init__(self, trace):
        self.trace = trace
        self.spans = load_host(trace.path)
        self.steps = steps_of(self.spans, trace.lo, trace.hi)
        self._scopes = None

    def scopes(self):
        """``(phase seconds, convolution seconds by phase, scoped share)``
        of the step module's ops, or ``(None, None, None)`` where the
        trace has no such module or no scope path."""
        if self._scopes is None:
            t = self.trace
            paths = {int(name.rsplit(":", 1)[1].split()[0]): table
                     for name, table in scope_paths(t.path).items()
                     if name.startswith("/device:TPU:")}
            ops = step_ops(t.ops, t.modules, t.lo, t.hi)
            self._scopes = seconds_by_phase(ops, paths) \
                if ops and any(paths.values()) else (None, None, None)
        return self._scopes

    def phase_ms(self, phase, conv=False):
        """Milliseconds a step in ``phase``: all of the step module's ops
        there, or with ``conv`` the convolutions among them."""
        found = self.scopes()[1 if conv else 0]
        return None if found is None else \
            1e3 * found[phase] / self.trace.steps

    def step_ms(self, names):
        return median_ms(per_step_seconds(self.steps, names))


def view(ctx):
    """The ``ProgramView`` of a reader's ``ctx``, or ``None`` where the run
    was not traced; built at the first call and kept on the trace."""
    t = ctx["trace"]
    if t is None:
        return None
    if not hasattr(t, "program_view"):
        t.program_view = ProgramView(t)
    return t.program_view


def conv_least_ms(ctx, passes):
    """``passes`` thirds (forward 1, backward 2) of the least time for the
    step's convolutions, as ``conv_roofline`` reckons it."""
    from benchmarks.harness import flops
    r, peaks = ctx["run"], ctx["peaks"]
    least = flops.conv_least_seconds(r["nodes"], r["node_shapes"],
                                     peaks["bf16_flops"],
                                     peaks["hbm_bytes_per_s"])[0]
    return 1e3 * passes * least / 3


def say(text):
    print("program_spans: " + text, file=sys.stderr)
