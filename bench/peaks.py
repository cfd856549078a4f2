"""Published peak rates per chip, keyed by ``jax.Device.device_kind``.

TPU v5e: Google Cloud documentation, "TPU v5e" — 197 TFLOP/s in bfloat16,
16 GB of HBM at 819 GB/s.  The bfloat16 rate is the chip's highest matmul
rate, so a least time taken from it bounds float32 work from below as well.
"""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
}


def peaks(device_kind: str) -> dict:
    """The peaks of ``device_kind``; an unknown kind is an error."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None


def least_time_s(flops: float, nbytes: float, pk: dict) -> tuple[float, str]:
    """The roofline's least time for the work, and which bound sets it."""
    t_flops = flops / pk["flops"]
    t_bytes = nbytes / pk["hbm_bytes_per_s"]
    return (t_bytes, "bytes") if t_bytes >= t_flops else (t_flops, "flops")
