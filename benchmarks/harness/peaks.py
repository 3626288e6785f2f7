"""The one table of device peaks the benchmark divides by.

Keyed by the `device_kind` JAX reports. A device that is not here is an
error, never a default: a roofline share over the wrong peak is worse
than none.
"""

from __future__ import annotations

# Google Cloud documentation, "TPU v5e" system architecture page: 197
# TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2e at 819 GB/s per chip.
DEVICE_PEAKS = {
    "TPU v5 lite": {
        "flops_per_s": 197e12,
        "int8_ops_per_s": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "cloud.google.com/tpu/docs/v5e (system architecture)",
    },
}


def peaks_for(device_kind: str) -> dict:
    try:
        return DEVICE_PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device_kind {device_kind!r}; add a "
            f"row with its source to benchmarks/harness/peaks.py"
        ) from None
