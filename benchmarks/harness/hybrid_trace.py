"""``lm_trace``'s times by symbol node over the *leaf* ops of the step: the
profiler files a ``while`` op and the ops of its body as events of one
line (PERF.md section 7.5), so a reader that adds up every event counts a
loop twice. Here an op whose interval holds another op of its device is
left out, and its body's ops stand for it."""
import types

from benchmarks.harness import lm_trace, program_spans as ps


def leaf_ops(ops):
    """``ops`` (``(device, name, category, start, seconds)``) without those
    inside whose interval another op of the same device starts."""
    order = sorted(ops, key=lambda o: (o[0], o[3], -o[4]))
    return [o for o, nxt in zip(order, order[1:] + [None])
            if nxt is None or nxt[0] != o[0] or nxt[3] >= o[3] + o[4]]


def view(ctx):
    """The ``lm_trace.NodeTimes`` of a reader's ``ctx`` over the leaf ops,
    or ``None`` where the run was not traced or the cell is no language
    model's."""
    t, run = ctx["trace"], ctx["run"]
    if t is None or "lm" not in run:
        return None
    if not hasattr(t, "leaf_node_times"):
        leaves = types.SimpleNamespace(**vars(t))
        leaves.ops = leaf_ops(t.ops)
        t.leaf_node_times = lm_trace.NodeTimes(leaves, run["nodes"])
        ps.say("leaf ops: %d of %d" % (len(leaves.ops), len(t.ops)))
        for node, ms in t.leaf_node_times.by_node().most_common(60):
            ps.say("leaf node %-24s %.3f ms" % (node, ms))
    return t.leaf_node_times
