"""Published peaks of the chips the benchmark runs on, keyed by JAX's
`device_kind`. A device missing here is an error, never a default."""
from __future__ import annotations

from typing import Dict

PEAKS: Dict[str, Dict[str, float]] = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 393 TOP/s
    # int8, 16 GB HBM at 819 GB/s per chip. Float32 matrix products at the
    # default precision run as bf16 passes, so the bf16 peak is the
    # compute roof for this program.
    "TPU v5 lite": {"flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
}


def peaks(device_kind: str) -> Dict[str, float]:
    """Peaks of `device_kind`; ValueError for a chip not in the table."""
    if device_kind not in PEAKS:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r}; known: {sorted(PEAKS)}")
    return PEAKS[device_kind]
