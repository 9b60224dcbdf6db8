"""Least time the chip could take for the step's state-space recurrences
(per mixer three passes, each the larger of the chunked form's FLOPs / peak
and the bytes of x, B, C, delta and y / bandwidth) over the device time
under the recurrence's nodes (``ssm.scan_ms``): it reads the same work
whatever implements it."""
from benchmarks.harness import hybrid_flops, hybrid_trace


def read(ctx):
    v = hybrid_trace.view(ctx)
    if v is None or ctx["peaks"] is None:
        return None
    ms = v.ms(("_mixer_ssd",))
    if not ms:
        return None
    lm, peaks = ctx["run"]["lm"], ctx["peaks"]
    least = hybrid_flops.ssd_least_seconds(
        hybrid_flops.model_of(ctx["cfg"], lm["model"]),
        lm["tokens"] // lm["seq_len"], lm["seq_len"], peaks["bf16_flops"],
        peaks["hbm_bytes_per_s"])[0]
    return 100.0 * 1e3 * least / ms
