"""Least time the chip could take for the step's attention products at
the LFM2 cell's head width (the unmasked scores and values, 32 query heads
on 8 K/V heads of 64; per attention layer forward, dQ, dK/dV; each the
larger of FLOPs / peak and the bytes of q, k, v, o / bandwidth) over the
device time in the attention kernels' ops (``flash_attention_*`` under
the ``*_attn_core`` nodes), per step."""
from benchmarks.harness import lfm2_flops, lm_trace


def read(ctx):
    v = lm_trace.view(ctx)
    if v is None or ctx["peaks"] is None:
        return None
    ms = v.ms(("_attn_core",), "flash_attention")
    if not ms:
        return None
    lm, peaks = ctx["run"]["lm"], ctx["peaks"]
    least = lfm2_flops.attention_least_seconds(
        lfm2_flops.model_of(ctx["cfg"], lm["model"]),
        lm["tokens"] // lm["seq_len"], lm["seq_len"], peaks["bf16_flops"],
        peaks["hbm_bytes_per_s"])[0]
    return 100.0 * 1e3 * least / ms
