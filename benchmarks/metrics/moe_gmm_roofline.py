"""Least time the chip could take for the routed rows' three products
(uniform load; per expert layer three passes, each the larger of FLOPs /
peak and bytes / bandwidth) over the device time in the grouped products'
ops (every ``ragged-dot`` call of the step), per step. The count reads the
same work whatever implements it."""
from benchmarks.harness import lm_flops, lm_trace


def read(ctx):
    v = lm_trace.view(ctx)
    if v is None or ctx["peaks"] is None:
        return None
    ms = v.grouped_ms()[0]
    if not ms:
        return None
    lm, peaks = ctx["run"]["lm"], ctx["peaks"]
    least = lm_flops.grouped_least_seconds(
        lm["model"], lm["tokens"], peaks["bf16_flops"],
        peaks["hbm_bytes_per_s"])[0]
    return 100.0 * 1e3 * least / ms
