"""Published per-chip peaks, keyed by ``jax.Device.device_kind``.

"TPU v5 lite" — Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16,
16 GB HBM at 819 GB/s, 1,600 Gbit/s of inter-chip interconnect. A kind
that is not in the table raises instead of borrowing another chip's
numbers.
"""

from __future__ import annotations

from typing import Dict

PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "ici_bytes_per_s": 1600e9 / 8},
}


def peaks(device_kind: str) -> Dict[str, float]:
    """The peak table row for a device kind; unknown kinds raise."""
    if device_kind not in PEAKS:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r}; known: {sorted(PEAKS)}")
    return PEAKS[device_kind]
