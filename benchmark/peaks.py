"""Published peaks of the chips the benchmark may run on, keyed by the
``device_kind`` JAX reports. A kind that is not here is an error, never a
default: a share of an unknown peak is no number.

Source of every row: Google Cloud documentation, "TPU v5e" system
architecture page (197 TFLOP/s bf16, 819 GB/s HBM, 16 GB HBM per chip).
(Arithmetic copied from ``bench.py:_published_peak``.)
"""

from __future__ import annotations

# device_kind (lower-cased substring) -> peaks
PEAKS = {
    "v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9,
                "source": "Google Cloud documentation, TPU v5e"},
    "v5e": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9,
            "source": "Google Cloud documentation, TPU v5e"},
}


def peaks_for(device_kind: str) -> dict:
    kind = str(device_kind).lower()
    for tag, row in PEAKS.items():
        if tag in kind:
            return row
    raise KeyError(
        f"no published peak for device_kind {device_kind!r}; add a row to "
        "benchmark/peaks.py with its source"
    )
