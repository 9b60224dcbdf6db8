"""``jit_compile`` spans inside the window; 0 expected, and a run with any
is failed by the window's own check."""


def read(ctx):
    return ctx["run"]["compiles_in_window"]
