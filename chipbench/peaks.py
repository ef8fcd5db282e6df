"""Published peaks of the chips the benchmark runs on, keyed by the
``device_kind`` that JAX reports.  A kind that is not here is an error:
no number is ever divided by a guessed peak.

Source: Google Cloud documentation, "TPU v5e" (system architecture):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB of HBM at 819 GB/s per chip.
"""
from __future__ import annotations

SOURCE = "Google Cloud documentation, TPU v5e system architecture"

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "int8_ops": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
    },
}


class UnknownDevice(KeyError):
    pass


def peaks_for(device_kind: str) -> dict:
    """The peaks of ``device_kind``; raises UnknownDevice otherwise."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise UnknownDevice(
            f"no published peaks for device kind {device_kind!r}; known: "
            f"{sorted(PEAKS)}") from None
