"""Least time the chip could take for the step's convolutions (forward,
data gradient, weight gradient of every Convolution node; each the larger
of FLOPs / peak and bytes / bandwidth) over the device time the trace
shows in ops whose XLA category is a convolution, per step."""
from benchmarks.harness import flops


def bounds(ctx):
    r, peaks = ctx["run"], ctx["peaks"]
    return flops.conv_least_seconds(r["nodes"], r["node_shapes"],
                                    peaks["bf16_flops"],
                                    peaks["hbm_bytes_per_s"])


def read(ctx):
    t = ctx["trace"]
    if t is None or ctx["peaks"] is None or not t.groups["conv"]:
        return None
    least, _, _ = bounds(ctx)
    return 100.0 * least * t.steps / t.groups["conv"]
