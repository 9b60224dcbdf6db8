"""Least time the chip could take for the step's latent-attention products
(the unmasked scores at the keys' width, 192, and values at theirs, 128;
per layer forward, dQ, dK/dV; each the larger of FLOPs / peak and the bytes
of q, k, v, o / bandwidth) over the device time in the attention kernels'
ops (``flash_attention_*`` under the ``*_attn_core`` nodes), per step."""
from benchmarks.harness import kimi_linear_flops, lm_trace


def read(ctx):
    v = lm_trace.view(ctx)
    if v is None or ctx["peaks"] is None:
        return None
    ms = v.ms(("_attn_core",), "flash_attention")
    if not ms:
        return None
    lm, peaks = ctx["run"]["lm"], ctx["peaks"]
    least = kimi_linear_flops.mla_attention_least_seconds(
        kimi_linear_flops.model_of(ctx["cfg"], lm["model"]),
        lm["tokens"] // lm["seq_len"], lm["seq_len"], peaks["bf16_flops"],
        peaks["hbm_bytes_per_s"])[0]
    return 100.0 * 1e3 * least / ms
