"""Operations and bytes that a model's step requires, counted from shapes.

The count is the algorithm's: one multiply-add is 2 FLOPs, a training
step is forward + data gradient + weight gradient (3 x forward for every
layer; the first layer's data gradient, which nobody needs, is counted
too, as is usual for "3 x forward"). Nothing that an implementation
recomputes, pads or fuses changes it.

Input is a symbol's node list (``json.loads(sym.tojson())["nodes"]``)
and the inferred shape of every node's first output, by node name.
"""
import ast
import math


def _ints(text):
    v = ast.literal_eval(text)
    return tuple(v) if isinstance(v, (tuple, list)) else (int(v),)


def matmul_layers(nodes, shapes):
    """One row per Convolution / FullyConnected node:
    ``(name, op, macs_forward, input_elems, weight_elems, output_elems)``.

    ``shapes[name]`` is the output shape of node ``name`` (for a variable,
    its own shape)."""
    rows = []
    for node in nodes:
        op = node["op"]
        if op not in ("Convolution", "FullyConnected"):
            continue
        src = nodes[node["inputs"][0][0]]["name"]
        wname = nodes[node["inputs"][1][0]]["name"]
        x, w, y = shapes[src], shapes[wname], shapes[node["name"]]
        if op == "Convolution":
            # y: (N, Cout, Ho, Wo); w: (Cout, Cin/groups, kh, kw)
            macs = math.prod(y) * math.prod(w[1:])
        else:
            # y: (N, out); w: (out, in)
            macs = y[0] * math.prod(w)
        rows.append((node["name"], op, macs, math.prod(x), math.prod(w),
                     math.prod(y)))
    return rows


def forward_macs(nodes, shapes):
    """Multiply-adds of one forward pass over the whole batch."""
    return sum(r[2] for r in matmul_layers(nodes, shapes))


def train_step_flops(nodes, shapes):
    """FLOPs one training step requires: 3 x forward, 2 per multiply-add."""
    return 3 * 2 * forward_macs(nodes, shapes)


def conv_least_seconds(nodes, shapes, peak_flops, peak_bytes_per_s,
                       act_bytes=2, weight_bytes=2):
    """The least time the chip could take for the step's convolutions.

    For each Convolution node three passes (forward, data gradient, weight
    gradient), each the larger of FLOPs / peak and bytes / bandwidth, where
    a pass reads two of (input, weight, output) and writes the third. Returns
    ``(seconds, seconds_if_compute_bound, seconds_if_memory_bound)`` so
    the caller can say which bound binds."""
    total = by_flops = by_bytes = 0.0
    for _, op, macs, x, w, y in matmul_layers(nodes, shapes):
        if op != "Convolution":
            continue
        t_flops = 2 * macs / peak_flops
        t_bytes = (act_bytes * (x + y) + weight_bytes * w) / peak_bytes_per_s
        total += 3 * max(t_flops, t_bytes)
        by_flops += 3 * t_flops
        by_bytes += 3 * t_bytes
    return total, by_flops, by_bytes
