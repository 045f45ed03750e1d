"""Published peaks of one chip, keyed by the ``device_kind`` string the
runtime reports. A device that is not in the table is an error, not a
default.

Source: Google Cloud documentation, "TPU v5e" system architecture page:
197 TFLOP/s bf16, 16 GB HBM2e at 819 GB/s per chip. "TPU v5 lite" is what
jax 0.9 / libtpu 0.0.34 report for a v5e (seen on the chip, PR 21). The
FLOP/s entries of the other kinds are copied from ``bench.PEAK_FLOPS``;
their bandwidths are from the same documentation set (v4: 1,200 GB/s,
v5p: 2,765 GB/s, v6e: 1,640 GB/s) and have not been seen here.
"""

PEAKS = {
    "TPU v4": {"flops": 275e12, "bytes_per_s": 1200e9},
    "TPU v5 lite": {"flops": 197e12, "bytes_per_s": 819e9},
    "TPU v5": {"flops": 459e12, "bytes_per_s": 2765e9},
    "TPU v6 lite": {"flops": 918e12, "bytes_per_s": 1640e9},
}


def peak(device_kind):
    if device_kind not in PEAKS:
        raise ValueError(
            f"chipbench.peaks: no peak recorded for device_kind "
            f"{device_kind!r}; add it to PEAKS with its source (known: "
            f"{sorted(PEAKS)})")
    return PEAKS[device_kind]
