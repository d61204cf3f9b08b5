"""Published peaks per device kind, as JAX names the kind.

Source: Google Cloud documentation, "TPU v5e": 197 TFLOP/s in bf16 and
819 GB/s of HBM bandwidth per chip.  A kind that is not here is an error,
never a default.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"flops_per_s": 197e12, "bytes_per_s": 819e9,
                    "source": 'Google Cloud documentation, "TPU v5e"'},
}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; add them to bench/peaks.py "
                       f"with their source") from None
