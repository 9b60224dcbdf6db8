"""Seconds in ``jit_compile`` spans before the window (compilation, or the
load from the persistent cache)."""


def read(ctx):
    return ctx["run"]["compile_setup_s"]
