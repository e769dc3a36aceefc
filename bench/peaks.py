"""Published peaks of the chips the benchmark runs on, keyed by the
``device_kind`` JAX reports. A kind that is not in the table is an error:
a roofline or utilization against a guessed peak means nothing.

Source: Google Cloud documentation, "TPU v5e" (system architecture): per
chip 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s.
"""
from __future__ import annotations

SOURCE = 'Google Cloud documentation, "TPU v5e" (per-chip peaks)'

_V5E = {"bf16_flops": 197e12, "int8_ops": 393e12,
        "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9}

PEAKS = {"TPU v5 lite": _V5E, "TPU v5e": _V5E}


def peaks_for(device_kind: str) -> dict:
    """The peak table of ``device_kind``; raises ``KeyError`` for a chip
    the table does not list."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}") from None


def least_time_s(peaks: dict, *, bf16_flops: float = 0.0,
                 int8_ops: float = 0.0, bytes_moved: float = 0.0) -> float:
    """Roofline least time: the larger of the compute bound (each dtype at
    its own peak) and the memory bound."""
    compute = bf16_flops / peaks["bf16_flops"] + int8_ops / peaks["int8_ops"]
    return max(compute, bytes_moved / peaks["hbm_bytes_per_s"])
