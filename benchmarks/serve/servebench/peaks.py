"""Published peaks of each accelerator the benchmark may run on.

Keyed by ``device_kind`` exactly as JAX reports it.  A kind that is not
in the table is an error: a roofline or utilisation against a guessed
peak would be a guess too.
"""

from __future__ import annotations

PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16,
    # 16 GB of HBM at 819 GB/s per chip.
    "TPU v5 lite": {
        "bf16_flop_s": 197e12,
        "hbm_bytes_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, TPU v5e",
    },
}


def peak(kind: str) -> dict:
    """The peak entry of ``kind``; raises ``KeyError`` for unknown kinds."""
    if kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {kind!r}; "
                       f"known: {sorted(PEAKS)}")
    return PEAKS[kind]


def least_time_s(flops: float, nbytes: float, kind: str) -> float:
    """The least time the chip could take for this work: the larger of
    operations over peak FLOP/s and bytes over peak bandwidth."""
    p = peak(kind)
    return max(flops / p["bf16_flop_s"], nbytes / p["hbm_bytes_s"])
