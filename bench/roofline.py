"""Peaks of the chips the benchmark runs on, and the work a kernel call
needs, counted from its shapes.

The counts are what a call needs, not what one implementation happens to
move: a later kernel that shares X across lanes still reads true against
them. A chip kind that is not in ``PEAKS`` is an error, not a default.
"""
from __future__ import annotations

#: per chip; source: Google Cloud documentation, "TPU v5e" (197 TFLOP/s
#: in bfloat16, 16 GB of HBM at 819 GB/s)
PEAKS = {
    "TPU v5 lite": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}"
                       f" (have {sorted(PEAKS)})") from None


def fused_smo_step(n: int, d: int, itemsize: int = 4) -> tuple[float, float]:
    """(flops, bytes) of one ``fused_smo_step`` call over X (n, d): the two
    kernel rows of the working pair need 2 x n x d multiply-adds; X is read
    once, with its n row norms, and the (n, 2) row pair is written once."""
    flops = 2.0 * 2 * n * d
    nbytes = float(itemsize) * (n * d + n + 2 * d + 2 * n)
    return flops, nbytes


def least_time(flops: float, nbytes: float, device_kind: str):
    """(seconds, bound): the least time the chip could take for the work,
    and which of its peaks binds (``"compute"`` or ``"memory"``)."""
    p = peaks(device_kind)
    t_c, t_m = flops / p["flops_per_s"], nbytes / p["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
