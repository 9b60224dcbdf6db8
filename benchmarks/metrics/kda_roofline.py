"""Least time the chip could take for the step's delta-rule recurrences
(per KDA layer three passes, each the larger of the chunked form's FLOPs /
peak and the bytes of q, k, v, g, beta and o / bandwidth) over the device
time under the recurrence's nodes (``kda.scan_ms``): it reads the same work
whatever implements it."""
from benchmarks.harness import hybrid_trace, kimi_linear_flops


def read(ctx):
    v = hybrid_trace.view(ctx)
    if v is None or ctx["peaks"] is None:
        return None
    ms = v.ms(("_kda_core",))
    if not ms:
        return None
    lm, peaks = ctx["run"]["lm"], ctx["peaks"]
    least = kimi_linear_flops.kda_least_seconds(
        kimi_linear_flops.model_of(ctx["cfg"], lm["model"]),
        lm["tokens"] // lm["seq_len"], lm["seq_len"], peaks["bf16_flops"],
        peaks["hbm_bytes_per_s"])[0]
    return 100.0 * 1e3 * least / ms
