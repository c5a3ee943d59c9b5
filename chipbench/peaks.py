"""Published peaks of each chip, keyed by JAX's ``device_kind``.

Source: Google Cloud documentation, "TPU v5e" (system architecture): per
chip 197 TFLOP/s bf16, 394 TOP/s int8, 16 GB of HBM2 at 819 GB/s.
A kind that is not in the table is an error, not a default.
"""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "bf16_flops_per_s": 197e12,
        "source": "Google Cloud documentation, TPU v5e",
    },
}


def for_kind(kind: str, strict: bool = True) -> dict:
    """The peaks of ``kind``; with ``strict=False`` (a run off the chip, in
    tests) an unknown kind gives an empty table and no share is read."""
    if kind in PEAKS:
        return PEAKS[kind]
    if strict:
        raise KeyError(f"no published peaks for device kind {kind!r}")
    return {}
