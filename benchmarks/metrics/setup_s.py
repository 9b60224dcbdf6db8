"""Process start to the request for the first timed batch: imports,
backend, weights and pool, bind, compilation or the cache, warm-up steps."""


def read(ctx):
    return ctx["run"]["setup_s"]
