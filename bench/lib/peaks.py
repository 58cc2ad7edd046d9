"""Published peaks of each accelerator, keyed by JAX's ``device_kind``.

Source: Google Cloud documentation, "TPU v5e" (system architecture page):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s per chip.
A device that is not in the table is an error, never a default.
"""
from __future__ import annotations

_V5E = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16 * 2 ** 30,
        "source": "Google Cloud documentation, 'TPU v5e'"}

PEAKS = {"TPU v5 lite": _V5E, "TPU v5e": _V5E}


def peak(kind: str) -> dict:
    if kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {kind!r}; "
                       f"known: {sorted(PEAKS)}")
    return PEAKS[kind]


def roofline_s(flops: float, nbytes: float, kind: str) -> float:
    """Least time the chip needs for the work: the larger of the compute
    and the memory bound."""
    p = peak(kind)
    return max(flops / p["bf16_flops"], nbytes / p["hbm_bytes_per_s"])
