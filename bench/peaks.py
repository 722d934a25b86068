"""Published peaks of one chip, keyed by the ``device_kind`` JAX reports.

A kind that is not in the table is an error, not a default.
"""

from __future__ import annotations

PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB HBM at 819 GB/s
    "TPU v5 lite": {"bf16_flop_per_s": 197e12, "hbm_bytes_per_s": 819e9},
}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device_kind {device_kind!r}; "
            f"bench/peaks.py knows {sorted(PEAKS)}"
        ) from None
