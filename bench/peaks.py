"""Published peaks of the devices the benchmark runs on, keyed by JAX's
``device_kind``.  A kind that is not in the table is an error, never a
default.

TPU v5e: Google Cloud documentation, "TPU v5e" (cloud.google.com/tpu/docs/v5e):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2 at 819 GB/s per chip.  No
peak is published for emulated float64 arithmetic, which is what the
evaluator's sweep runs, so the sweep's roofline share has no compute bound.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 393e12,
        "source": "Google Cloud documentation, TPU v5e",
    },
}


def peaks(device_kind: str) -> dict:
    """The peak row of ``device_kind``; KeyError names the known kinds."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; known: "
            f"{sorted(PEAKS)}") from None
