#!/usr/bin/env python3
"""Print what a ``.xplane.pb`` holds: planes, lines, event counts, and a
few events of each line with their stats. For looking at a trace by hand
before trusting ``harness/trace_reduce.py`` on it.

    python3 benchmarks/tools/trace_dump.py <trace dir or .xplane.pb> [n]
"""
import collections
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
from benchmarks.harness import trace_reduce as tr


def main(argv):
    from jax.profiler import ProfileData
    path = argv[1]
    n = int(argv[2]) if len(argv) > 2 else 4
    if os.path.isdir(path):
        path = tr.find_xplane(path)
    print(path, os.path.getsize(path), "bytes")
    data = ProfileData.from_file(path)
    for plane in data.planes:
        print("PLANE %r" % plane.name)
        for line in plane.lines:
            events = list(line.events)
            if not events:
                continue
            total = sum(e.duration_ns for e in events) * 1e-9
            print("  LINE %r: %d events, %.4f s, first start %.6f s"
                  % (line.name, len(events), total,
                     events[0].start_ns * 1e-9))
            names = collections.Counter(e.name for e in events)
            print("    names:", names.most_common(6))
            for e in events[:n]:
                print("    %r start %.6f dur %.6f stats %r"
                      % (e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9,
                         dict(list(e.stats)[:12])))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
