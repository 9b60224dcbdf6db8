"""Share of the held rows that lay past their layer-step's first chunk,
over the compared steps and the expert layers (the program's
``moe.rows_overflow`` and ``moe.rows_held`` counters), as a share of 1:
0 where no step routed more than the even share here."""


def read(ctx):
    lm = ctx["run"].get("lm")
    if lm is None or not lm["moe"].get("moe.rows_held") \
            or "moe.rows_overflow" not in lm["moe"]:
        return None
    return lm["moe"]["moe.rows_overflow"] / lm["moe"]["moe.rows_held"]
