"""Published per-chip peaks, keyed by the ``device_kind`` JAX reports.

Copied from the program's ``repro/launch/roofline.py:PEAKS``. TPU v5e
(JAX's "TPU v5 lite"): 197 TFLOP/s bf16 and 819 GB/s HBM (Google Cloud
documentation, "TPU v5e"). A kind that is not in the table is an error,
never a default.
"""
from __future__ import annotations

PEAKS = {"TPU v5 lite": dict(flops=197e12, hbm_bw=819e9)}


def peaks(device_kind: str) -> dict:
    """The entry for ``device_kind``; raises ``KeyError`` for an unknown kind."""
    if device_kind not in PEAKS:
        raise KeyError(f"no peaks for device kind {device_kind!r} "
                       f"(known: {sorted(PEAKS)})")
    return PEAKS[device_kind]
