"""Rows of the fullest held expert over the mean of the held experts, over
the compared steps and the expert layers (the program's ``moe.rows_max``
and ``moe.rows_held`` counters): 1.0 is a perfectly even load."""


def read(ctx):
    lm = ctx["run"].get("lm")
    if lm is None or not lm["moe"].get("moe.rows_held"):
        return None
    held = lm["model"]["experts_held"][1]
    return lm["moe"]["moe.rows_max"] * held / lm["moe"]["moe.rows_held"]
