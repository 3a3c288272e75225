"""Published peaks of the chips the benchmark runs on, keyed by the
``device_kind`` JAX reports. A kind that is not here is an error: a
roofline share or an MFU against a guessed peak means nothing."""

from __future__ import annotations

from typing import Dict, NamedTuple


class Peak(NamedTuple):
    flops: float        # dense bf16 FLOP/s of one chip
    hbm_bytes: float    # HBM bytes/s of one chip
    hbm_capacity: int   # HBM bytes of one chip
    source: str


PEAKS: Dict[str, Peak] = {
    "TPU v5 lite": Peak(
        flops=197e12, hbm_bytes=819e9, hbm_capacity=16 * 10 ** 9,
        source="Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, "
               "16 GB HBM at 819 GB/s per chip"),
}


def peak_for(kind: str) -> Peak:
    """The peak of ``kind``; raises ``KeyError`` naming the known kinds."""
    if kind not in PEAKS:
        raise KeyError(f"no published peak for device kind {kind!r}; "
                       f"known: {sorted(PEAKS)}")
    return PEAKS[kind]
