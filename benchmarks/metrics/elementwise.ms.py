"""Device time per step in every op that is not a convolution: loop
fusions, copies, formatting, reductions."""


def read(ctx):
    t = ctx["trace"]
    if t is None:
        return None
    return 1e3 * t.groups["other"] / t.steps
