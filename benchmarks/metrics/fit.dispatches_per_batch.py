"""``dispatch.*`` counter delta over the window / batches: 1.0 when the
step is fused, 3 phase-split."""


def read(ctx):
    r = ctx["run"]
    return sum(r["dispatches"].values()) / r["steps"] if r["steps"] else None
