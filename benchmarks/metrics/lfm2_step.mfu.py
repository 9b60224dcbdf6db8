"""The whole step's share of the chip's peak in the LFM2 cell: FLOPs
``lfm2_flops`` counts from the configuration (3 x forward, 2 per
multiply-add; only unmasked query-key pairs; routed rows at their uniform
load; the short convolutions' elementwise work not counted) over seconds x
chips x peak bf16 FLOP/s, the steps and the host clock's seconds of the
traced part as ``lm_step.mfu`` takes them."""
from benchmarks.harness import lfm2_flops


def read(ctx):
    r, peaks = ctx["run"], ctx["peaks"]
    tr, lm = r["trace"], r.get("lm")
    if peaks is None or tr is None or lm is None:
        return None
    need = lfm2_flops.train_step_flops(
        lfm2_flops.model_of(ctx["cfg"], lm["model"]),
        lm["tokens"] // lm["seq_len"], lm["seq_len"]) * tr["steps"]
    return 100.0 * need / ((tr["t1"] - tr["t0"]) * r["chips"]
                           * peaks["bf16_flops"])
