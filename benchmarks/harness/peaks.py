"""Published per-chip peaks, keyed by the `device_kind` string the chip
reports. A copy of the row in `paddle_tpu/device/peaks.py` (the program
may change; the yardstick may not). A device that is not in the table
has no peak: `peaks_for` raises, it never defaults.

Source: Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16,
393 TOP/s int8, 16 GB HBM2e at 819 GB/s. The key is what a v5e reports
through jax 0.9.0 / libtpu 0.0.34 (chip run, PR 21).
"""

DEVICE_PEAKS = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "int8_ops": 393e12,
        "hbm_bytes": 16e9,
        "hbm_bytes_per_s": 819e9,
        "source": "Google Cloud documentation, 'TPU v5e'",
    },
}


def peaks_for(device_kind):
    try:
        return DEVICE_PEAKS[device_kind]
    except KeyError:
        raise LookupError(
            f"no published peaks for device_kind {device_kind!r} (known: "
            f"{sorted(DEVICE_PEAKS)}); add the row with its source to "
            "benchmarks/harness/peaks.py") from None
