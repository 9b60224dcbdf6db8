"""All images of all steps that completed in the window, over the whole
window: from the request for the first timed batch to the moment the last
step's output is ready (host clock; the drain is inside)."""


def read(ctx):
    r = ctx["run"]
    return r["steps"] * r["batch"] / r["window_s"] if r["steps"] else None
