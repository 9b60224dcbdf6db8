"""Chunks of the sorted order each of an expert layer's row passes ran a
step, over the compared steps and the expert layers (the program's
``moe.chunks_run`` and ``moe.steps`` counters): the chunks a layer-step's
held rows fill and the one written as nought after them, so 2.0 where
every layer-step's held rows fit one chunk, the even share of the
selections (1.0 where a layer holds every expert: its one chunk is the
whole order); each further chunk is the row passes' body once more."""


def read(ctx):
    lm = ctx["run"].get("lm")
    if lm is None or not lm["moe"].get("moe.steps") \
            or "moe.chunks_run" not in lm["moe"]:
        return None
    return lm["moe"]["moe.chunks_run"] / lm["moe"]["moe.steps"]
