"""95th percentile, over ALL steps of the window, of the time a step took
to complete after the one before it, as the watcher thread stamps the
completions.

The host's clock is good to some half a millisecond, so no single reading
may span less than 250 ms: the time is read over ``k`` consecutive steps
(every run of ``k``, sliding by one, so each step is in ``k`` readings) and
given per step, with ``k`` the least number for which ``k`` median steps
last 250 ms. A stall of the host, the feed or a compile in the window still
shows, a ``k``-th of it in each of ``k`` readings."""
import math
import statistics

from benchmarks.harness.device import percentile

LEAST_SPAN_S = 0.25


def read(ctx):
    gaps = list(ctx["run"]["step_gaps_s"])
    if len(gaps) < 2:
        return None
    k = max(1, math.ceil(LEAST_SPAN_S / statistics.median(gaps)))
    if len(gaps) < k:
        return None
    spans, total = [], sum(gaps[:k])
    spans.append(total)
    for i in range(k, len(gaps)):
        total += gaps[i] - gaps[i - k]
        spans.append(total)
    return 1e3 * percentile(spans, 95) / k
