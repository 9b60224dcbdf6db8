"""CPU time of the thread that runs ``Module.fit`` per batch (iterator,
feed, dispatch, metric bookkeeping, callback), mean over the window.
Thread CPU time, not the wall clock: the loop waits on the runtime's queue
once it is 32 steps ahead, and that wait is not the host's work. The mean
and not the median: the thread clock ticks in 10 ms on the chip's host, so
a single batch reads 0 or 10."""


def read(ctx):
    v = ctx["run"]["cpu_per_batch_s"]
    return 1e3 * float(sum(v)) / len(v) if len(v) else None
