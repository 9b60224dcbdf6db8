"""One traced window, loaded once and shared by the metric readers."""
from benchmarks.harness import trace_reduce as tr

HOST_SPANS = ("bench.window", "feed.next", "fit.batch_end", "drain")


class TraceView:
    def __init__(self, trace, chips):
        self.steps = trace["steps"]
        self.chips = chips
        self.path = tr.find_xplane(trace["dir"])
        self.ops, self.modules, self.spans = tr.load(self.path, HOST_SPANS)
        win = [s for s in self.spans if s[0] == "bench.window"]
        if not win:
            raise RuntimeError("no bench.window span in the trace")
        self.lo = win[0][1]
        self.hi = win[0][1] + win[0][2]
        self.window_s = self.hi - self.lo
        self.groups = tr.seconds_by_group(self.ops, self.lo, self.hi)

    def faults(self, nodes):
        """Messages for a failed run where the categories cannot be
        trusted (``nodes``: the symbol's node list)."""
        return tr.category_faults(
            self.groups, any(n["op"] == "Convolution" for n in nodes))

    def device_fields(self):
        busy = tr.busy_by_device(self.ops, self.lo, self.hi)
        return {"busy_s": sum(busy.values()) / max(len(busy), 1),
                "window_s": self.window_s}

    def breakdown(self):
        labelled = [s for s in self.spans if s[0] != "bench.window"]
        return {"device_ops": tr.top_ops(self.ops, self.lo, self.hi),
                "idle_gaps": tr.idle_gaps(self.ops, labelled, self.lo,
                                          self.hi)}
