"""Published peaks of the chips the benchmark runs on, keyed by the
``device_kind`` that JAX reports.  A kind missing here is an error: a
roofline share against a guessed peak would mean nothing."""
from __future__ import annotations

PEAKS = {
    # Google Cloud documentation, "TPU v5e" (cloud.google.com/tpu/docs/v5e):
    # 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2 at 819 GB/s per chip.
    "TPU v5 lite": {"flops": 197e12, "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9},
}


class UnknownDevice(KeyError):
    """The device kind has no entry in :data:`PEAKS`."""


def lookup(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise UnknownDevice(
            f"no published peaks for device kind {device_kind!r}; add them to "
            f"bench/peaks.py with their source (known: {sorted(PEAKS)})"
        ) from None
