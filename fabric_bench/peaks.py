"""Published peaks of each chip, keyed by JAX's ``device_kind``.

A device that is not in the table is an error, never a default.
"""
from __future__ import annotations

PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 393 TOP/s
    # int8, 16 GB HBM at 819 GB/s.  No float32 peak is published; the
    # bf16 peak is the larger, so a share against it is never overstated.
    "TPU v5 lite": {"flops": 197e12, "hbm_bytes_per_s": 819e9},
}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r} (have {sorted(PEAKS)})") from None


def least_seconds(flops: float, nbytes: float, device_kind: str):
    """The least time the chip could take for this work, and which bound
    gives it (``"flops"`` or ``"bytes"``)."""
    p = peaks(device_kind)
    t_f, t_b = flops / p["flops"], nbytes / p["hbm_bytes_per_s"]
    return (t_f, "flops") if t_f >= t_b else (t_b, "bytes")
