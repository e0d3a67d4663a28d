"""Published peaks per chip, keyed by JAX's ``device_kind``.

Source: Google Cloud TPU v5e documentation ("TPU v5e": 197 TFLOP/s
bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s).  The binary networks'
products are +-1 (and 8-bit for the first layer), exact in int8, so the
int8 MXU rate is the chip's highest published rate for that math.  A
device that is not in the table is an error, not a default.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"ops_per_s": 393e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
}


def peak(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; add them to bench/peaks.py "
                       f"with their source") from None


def least_time(ops: float, nbytes: float, device_kind: str) -> float:
    """The least time the chip could take: the larger of the compute
    bound and the memory bound."""
    p = peak(device_kind)
    return max(ops / p["ops_per_s"], nbytes / p["hbm_bytes_per_s"])
