"""One table of the chips' published peaks, keyed by ``device_kind``.

A device that is not in the table is an error, never a default: a share
of a peak worked out against the wrong peak is worse than none.
"""

PEAKS = {
    # Google Cloud documentation, "TPU v5e" (system architecture page):
    # 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2e at 819 GB/s per chip.
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "int8_ops": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud docs, TPU v5e: 197 TFLOP/s bf16, "
                  "819 GB/s HBM, 16 GB",
    },
}
PEAKS["TPU v5e"] = PEAKS["TPU v5 lite"]


def peaks(device_kind):
    """The peaks of one chip of ``device_kind``; raises on an unknown kind."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            "no published peaks for device kind %r in benchmarks/harness/"
            "peaks.py: add a row with its source" % (device_kind,)) from None
