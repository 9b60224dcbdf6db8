"""Least time the chip could take for the step's gated short convolutions
(per mixer three passes, each the larger of the gates' and taps' FLOPs /
peak and the bytes of the (T, 3 d) input and the (T, d) output /
bandwidth) over the device time in the leaf ops under the
``*_shortconv`` nodes alone (the projections around them are matrix
products like any other), per step."""
from benchmarks.harness import hybrid_trace, lfm2_flops


def read(ctx):
    v = hybrid_trace.view(ctx)
    if v is None or ctx["peaks"] is None:
        return None
    ms = v.ms(("_shortconv",))
    if not ms:
        return None
    lm, peaks = ctx["run"]["lm"], ctx["peaks"]
    least = lfm2_flops.shortconv_least_seconds(
        lfm2_flops.model_of(ctx["cfg"], lm["model"]),
        lm["tokens"] // lm["seq_len"], lm["seq_len"], peaks["bf16_flops"],
        peaks["hbm_bytes_per_s"])[0]
    return 100.0 * 1e3 * least / ms
