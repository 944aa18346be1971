"""Published per-chip peaks, keyed by the `device_kind` string the chip
reports (`jax.devices()[0].device_kind`) — the ONE denominator table
for every utilization this repo prints (`bench.py`, `tools/`, the
`pt_train_mfu` gauge). A device that is not in the table has no peak:
`peaks_for` raises, it does not default, so a CPU run can never be
divided by a TPU's number.
"""

__all__ = ["DEVICE_PEAKS", "UnknownDeviceKind", "peaks_for",
           "running_device_peaks"]

# bf16_flops / int8_ops per second, HBM bytes and bytes per second, for
# ONE chip. Source: Google Cloud documentation, "TPU v5e" (197 TFLOP/s
# bf16, 393 TOP/s int8, 16 GB HBM2e at 819 GB/s); the key is what a v5e
# reports through jax 0.9.0 / libtpu 0.0.34 (chip_smoke.py run, PR 21).
DEVICE_PEAKS = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "int8_ops": 393e12,
        "hbm_bytes": 16e9,
        "hbm_bytes_per_s": 819e9,
        "source": "Google Cloud documentation, 'TPU v5e'",
    },
}


class UnknownDeviceKind(LookupError):
    """No published peak for this `device_kind`."""


def peaks_for(device_kind):
    """The peaks row of `device_kind`; UnknownDeviceKind when the table
    has none (add the row with its source — never a fallback)."""
    try:
        return DEVICE_PEAKS[device_kind]
    except KeyError:
        raise UnknownDeviceKind(
            f"no published peaks for device_kind {device_kind!r} "
            f"(known: {sorted(DEVICE_PEAKS)}); utilization against a "
            "peak is defined only for a device in "
            "paddle_tpu/device/peaks.py") from None


def running_device_peaks():
    """`peaks_for` the device JAX is running on (ONE chip's row)."""
    import jax

    return peaks_for(jax.devices()[0].device_kind)
