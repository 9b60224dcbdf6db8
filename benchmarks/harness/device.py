"""The ``device`` entry of a result line, as JAX reports the device."""
import math


def device_entry(dev, chips, memory_peak_bytes):
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": int(chips), "memory_peak_bytes": int(memory_peak_bytes)}


def percentile(values, q):
    """The ``q``-th percentile (0-100) by the nearest-rank rule on sorted
    values: the smallest value with at least q % of the values at or below
    it. ``None`` for no values."""
    vals = sorted(values)
    if not vals:
        return None
    k = max(0, min(len(vals) - 1, math.ceil(q / 100.0 * len(vals)) - 1))
    return vals[k]
