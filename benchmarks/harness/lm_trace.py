"""Device time of a language model's step by symbol node, for the metrics
of attention and of the routed experts.

Built on ``program_spans``: the step module's ops inside the traced
window, each with its scope path. A path names its node somewhere along
it, also under ``checkpoint`` and ``transpose(jvp(...))`` wrappers, so the
node is the first component that is a name of the symbol's op nodes. A
program without scopes (the parent of the PR that added them) or a run
without a trace gives ``None``.
"""
import collections

from benchmarks.harness import program_spans as ps


def _parts(path):
    """The components of a scope path, wrappers such as ``jvp(...)`` and
    ``transpose(...)`` taken off."""
    for part in filter(None, (path or "").rstrip(":").split("/")):
        while "(" in part and part.endswith(")"):
            part = part[part.index("(") + 1:-1]
        yield part


class NodeTimes:
    """``rows``: ``(node, scope path, instruction name, seconds a step)`` of every
    op of the step module; ``node`` is ``""`` where the path names none of
    the symbol's nodes."""

    def __init__(self, trace, nodes):
        names = {n["name"] for n in nodes if n["op"] != "null"}
        paths = {int(name.rsplit(":", 1)[1].split()[0]): table
                 for name, table in ps.scope_paths(trace.path).items()
                 if name.startswith("/device:TPU:")}
        ops = ps.step_ops(trace.ops, trace.modules, trace.lo, trace.hi)
        devices = max(len({o[0] for o in ops}), 1)
        self.rows = []
        for dev, name, _, _, d in ops:
            path = paths.get(dev, {}).get(name) or ""
            node = next((p for p in _parts(path) if p in names), "")
            # on the chip an op's name is its whole HLO line, operands and
            # all: the instruction's own name is what stands before " = "
            self.rows.append((node, path, name.split(" = ")[0],
                              d / devices / trace.steps))

    def ms(self, node_has, path_has=None):
        """Milliseconds a step in the ops whose node's name contains one
        of ``node_has`` and, if given, whose scope path or op name
        contains ``path_has``; ``None`` where no op matches."""
        hit = [s for node, path, name, s in self.rows
               if any(h in node for h in node_has)
               and (path_has is None or path_has in path
                    or path_has in name)]
        return 1e3 * sum(hit) if hit else None

    def grouped_ms(self):
        """``(all, under a *_moe node)``: milliseconds a step in the routed
        layer's grouped products, found by the product's own name
        (``ragged-dot``): XLA gives these calls no scope path on the chip,
        so a node's time may not hold them; the second number is the part
        that ``ms(("_moe",))`` counts too."""
        hit = [(s, "_moe" in node) for node, _, name, s in self.rows
               if "ragged-dot" in name]
        if not hit:
            return None, None
        return (1e3 * sum(s for s, _ in hit),
                1e3 * sum(s for s, under in hit if under))

    def by_node(self):
        out = collections.Counter()
        for node, _, _, s in self.rows:
            out[node or "(no node in the scope path)"] += 1e3 * s
        return out


def view(ctx):
    """The ``NodeTimes`` of a reader's ``ctx``, or ``None`` where the run
    was not traced or the cell is no language model's."""
    t, run = ctx["trace"], ctx["run"]
    if t is None or "lm" not in run:
        return None
    if not hasattr(t, "node_times"):
        t.node_times = NodeTimes(t, run["nodes"])
        for node, ms in t.node_times.by_node().most_common(40):
            ps.say("node %-24s %.3f ms" % (node, ms))
    return t.node_times
