"""Published peaks of the chips the benchmark runs on, keyed by the
``device_kind`` that JAX reports.

TPU v5e (JAX: "TPU v5 lite"): 197 TFLOP/s in bf16 and 819 GB/s of HBM
bandwidth, from Google Cloud's documentation, "TPU v5e".
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
                    "source": "Google Cloud documentation, TPU v5e"},
}


def peak(device_kind: str) -> dict:
    """The peaks of ``device_kind``; a chip not in the table is an error."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peak for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None
