"""From a profiler trace to per-layer numbers.

Two halves. ``load`` reads an ``.xplane.pb`` with ``jax.profiler.
ProfileData`` into plain tuples; everything else works on those tuples,
so the arithmetic is tested on hand-built lists.

A device op is ``(device, name, category, start_s, duration_s)``; a host
span is ``(name, start_s, duration_s)``; a module run is ``(device, name,
start_s, duration_s)``. ``category`` is the op's ``hlo_category`` as XLA
wrote it into the op's metadata (read by ``xplane_meta``: ``ProfileData``
does not hand it out), never a guess from the name; ops without one get
``"uncategorised"``. On the chip an op's name is its whole HLO line
(``%fusion.12 = bf16[...] fusion(...), kind=kOutput, ...``).
"""
import collections
import glob
import os
import statistics

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
CATEGORY_STAT = "hlo_category"
#: least share of device time whose ops must carry a category
MIN_CATEGORISED = 0.95


def find_xplane(trace_dir):
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError("no .xplane.pb under %s" % trace_dir)
    return paths[-1]


def load(path, host_names=()):
    """``(device_ops, module_runs, host_spans)`` of one ``.xplane.pb``.

    Device planes are those named ``/device:TPU:<n>``; host spans are the
    events of any host line whose name is in ``host_names``."""
    from jax.profiler import ProfileData
    from benchmarks.harness import xplane_meta

    data = ProfileData.from_file(path)
    categories = xplane_meta.categories(path, CATEGORY_STAT)
    ops, modules, spans = [], [], []
    wanted = set(host_names)
    for plane in data.planes:
        name = plane.name
        if name.startswith("/device:TPU:"):
            dev = int(name.rsplit(":", 1)[1].split()[0])
            table = categories.get(name, {})
            for line in plane.lines:
                if line.name == OPS_LINE:
                    for ev in line.events:
                        cat = table.get(ev.name, "uncategorised")
                        ops.append((dev, ev.name, cat,
                                    ev.start_ns * 1e-9,
                                    ev.duration_ns * 1e-9))
                elif line.name == MODULES_LINE:
                    for ev in line.events:
                        modules.append((dev, ev.name, ev.start_ns * 1e-9,
                                        ev.duration_ns * 1e-9))
        elif name.startswith("/host:") and wanted:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in wanted:
                        spans.append((ev.name, ev.start_ns * 1e-9,
                                      ev.duration_ns * 1e-9))
    return ops, modules, spans


# -- interval arithmetic -----------------------------------------------------

def union(intervals):
    """Merged, sorted ``[(start, end)]`` of possibly overlapping ones."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def length(intervals):
    return sum(e - s for s, e in intervals)


def subtract(a, b):
    """The part of merged intervals ``a`` that merged ``b`` do not cover."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


# -- reductions --------------------------------------------------------------

def is_conv(category):
    return "convolution" in category.lower()


def devices_of(ops):
    return sorted({o[0] for o in ops})


def busy_by_device(ops, lo, hi):
    """Seconds with an op running, per device, inside ``[lo, hi]``."""
    per = collections.defaultdict(list)
    for dev, _, _, s, d in ops:
        per[dev].append((s, s + d))
    return {dev: length(clip(union(iv), lo, hi)) for dev, iv in per.items()}


def idle_share(ops, lo, hi):
    """1 - busy / window on the worst device, as a share of 1."""
    busy = busy_by_device(ops, lo, hi)
    if not busy or hi <= lo:
        return None
    return 1.0 - min(busy.values()) / (hi - lo)


def seconds_by_group(ops, lo, hi):
    """Device seconds in convolutions and in everything else, summed over
    ops that start inside ``[lo, hi]``, mean over devices, and the share
    of that time which carried an XLA category."""
    devs = devices_of(ops)
    conv = other = categorised = total = 0.0
    for dev, name, cat, s, d in ops:
        if not lo <= s <= hi:
            continue
        total += d
        if cat != "uncategorised":
            categorised += d
        if is_conv(cat):
            conv += d
        else:
            other += d
    n = max(len(devs), 1)
    return dict(conv=conv / n, other=other / n, total=total / n,
                categorised_share=(categorised / total) if total else None)


def category_faults(groups, has_convolutions):
    """Why the split by category cannot be trusted, as messages (none if
    it can). An op whose category was not found counts as "everything
    else": if the metadata is missed for some convolutions their time
    leaves the convolutions' group, which flatters ``conv_roofline``; if
    for all, the metric would fall silent."""
    faults = []
    share = groups["categorised_share"]
    if share is None or share < MIN_CATEGORISED:
        faults.append(
            "XLA's %s was found for %s of the device's busy time, under %g"
            % (CATEGORY_STAT,
               "none" if share is None else "%.4f" % share, MIN_CATEGORISED))
    if has_convolutions and not groups["conv"]:
        faults.append("the model has Convolution nodes and the trace no op "
                      "of a convolution category")
    return faults


def step_module(modules, lo, hi):
    """The runs, inside the window, of the module that takes most of the
    device time there: the step program. ``(name, [durations of device 0's
    runs])`` or ``None``."""
    total = collections.Counter()
    for dev, name, s, d in modules:
        if lo <= s <= hi:
            total[name] += d
    if not total:
        return None
    name = total.most_common(1)[0][0]
    dev0 = min(m[0] for m in modules)
    runs = [d for dev, n, s, d in modules
            if n == name and dev == dev0 and lo <= s <= hi]
    return name, runs


def top_ops(ops, lo, hi, n=10):
    """The ``n`` op groups (XLA category, then the instruction's name
    with its numeric suffix cut) that took most device time, in seconds a
    device."""
    total = collections.Counter()
    for dev, name, cat, s, d in ops:
        if lo <= s <= hi:
            stem = name.split(" = ")[0].lstrip("%").split(".")[0] or name
            total["%s: %s" % (cat, stem)] += d
    devs = max(len(devices_of(ops)), 1)
    return [[k, v / devs] for k, v in total.most_common(n)]


def idle_gaps(ops, spans, lo, hi, n=10):
    """The longest idle gaps of the first device, each labelled with the
    host span that covers most of it (or ``"host: unlabelled"``), grouped
    by label: ``[[label, seconds]]``."""
    devs = devices_of(ops)
    if not devs:
        return []
    dev0 = devs[0]
    busy = clip(union([(s, s + d) for dev, _, _, s, d in ops if dev == dev0]),
                lo, hi)
    gaps = subtract([(lo, hi)], busy)
    total = collections.Counter()
    for gs, ge in gaps:
        best, best_cover = "host: unlabelled", 0.0
        for name, s, d in spans:
            cover = min(ge, s + d) - max(gs, s)
            if cover > best_cover:
                best, best_cover = name, cover
        total[best] += ge - gs
    return [[k, v] for k, v in total.most_common(n)]


def median(values):
    return statistics.median(values) if values else None
