"""Least time the chip could take for the step's attention products (the
unmasked scores and values; per layer forward, dQ, dK/dV; each the larger
of FLOPs / peak and the bytes of q, k, v, o / bandwidth) over the device
time in the attention kernels' ops (``flash_attention_*``), per step."""
from benchmarks.harness import lm_flops, lm_trace


def read(ctx):
    v = lm_trace.view(ctx)
    if v is None or ctx["peaks"] is None:
        return None
    ms = v.ms(("_attn_core",), "flash_attention")
    if not ms:
        return None
    lm, peaks = ctx["run"]["lm"], ctx["peaks"]
    least = lm_flops.attention_least_seconds(
        lm["model"], lm["tokens"] // lm["seq_len"], lm["seq_len"],
        peaks["bf16_flops"], peaks["hbm_bytes_per_s"])[0]
    return 100.0 * 1e3 * least / ms
