"""The whole step's share of the chips' peak: FLOPs the window's steps
require (3 x forward, 2 per multiply-add, from the symbol's shapes) over
seconds x chips x peak bf16 FLOP/s. Per-layer metrics come from the traced
run, whose window the profiler's stop interrupts: so the steps and the host
clock's seconds of the traced part, from the request for the first batch
to the last traced step's output (the pipeline's fill is inside)."""
from benchmarks.harness import flops


def read(ctx):
    r, peaks = ctx["run"], ctx["peaks"]
    tr = r["trace"]
    if peaks is None or tr is None:
        return None
    need = flops.train_step_flops(r["nodes"], r["node_shapes"]) * tr["steps"]
    return 100.0 * need / ((tr["t1"] - tr["t0"]) * r["chips"]
                           * peaks["bf16_flops"])
