"""Published per-chip peaks, keyed by ``jax.Device.device_kind``.

TPU v5e: Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16,
393 TOP/s int8, 16 GB of HBM at 819 GB/s.  The FLOP/s peak is the matrix
unit's bf16 rate, the highest the chip has: a roofline time taken from it
is a lower bound for work of any precision, so a share of the roofline
against it never overstates.  A kind not in the table has no roofline.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
}


def peaks(device_kind: str, chips: int = 1) -> dict:
    """The peaks of ``chips`` devices of ``device_kind`` together (a mesh's
    work may use all of them at once); raises KeyError for an unknown
    kind."""
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r} (known: {sorted(PEAKS)})")
    return {k: chips * v for k, v in PEAKS[device_kind].items()}
