"""Fig. 16 — plane-level compressibility (ZSTD, 4 KB blocks).

Paper: high-order exponent planes are consistently the most compressible;
KV exponent planes benefit further from channel grouping + exponent delta.
"""

from __future__ import annotations

import numpy as np

from repro.core import synth
from repro.core.bitplane import pack_planes
from repro.core.codec import compress_slab
from repro.core.kv_transform import kv_forward

from .common import emit


def _plane_ratios(u16: np.ndarray) -> list[float]:
    """Per-plane ZSTD ratio over 4 KB blocks of a flat u16 stream."""
    total_raw = np.zeros(16)
    total_comp = np.zeros(16)
    flat = u16.ravel()
    for s in range(0, flat.size - 2047, 2048):
        planes = pack_planes(flat[s : s + 2048])
        n = planes.shape[1]
        bounds = np.arange(17) * n
        comps, _ = compress_slab(planes.reshape(-1), bounds[:-1], bounds[1:],
                                 "zstd")
        total_raw += n
        total_comp += [len(c) for c in comps]
    return list(total_raw / np.maximum(total_comp, 1))


def run():
    # BF16 weights
    w = synth.weights(1 << 20, "bf16", seed=3)
    r = _plane_ratios(w)
    exp_mean = float(np.mean(r[7:15]))
    man_mean = float(np.mean(r[0:7]))
    emit("fig16", "weights_bf16_exp_planes_mean_ratio", exp_mean, "x",
         "paper: exponent planes dominate")
    emit("fig16", "weights_bf16_man_planes_mean_ratio", man_mean, "x",
         "mantissa ~ noise (ratio ~1)")
    emit("fig16", "weights_bf16_sign_plane_ratio", r[15], "x")
    assert exp_mean > man_mean, "exponent planes must dominate"

    # quantized weights — headroom narrows (paper)
    for fmt in ("fp8", "int4"):
        u = synth.weights(1 << 20, fmt, seed=3)
        r_q = _plane_ratios(u)
        emit("fig16", f"weights_{fmt}_exp_planes_mean_ratio",
             float(np.mean(r_q[7:15])), "x", "narrower than bf16")

    # KV: raw token-major planes vs TRACE-transformed planes
    kv = synth.kv_cache(2048, 512, seed=4)
    r_raw = _plane_ratios(kv)
    stream, _ = kv_forward(kv)
    r_tr = _plane_ratios(stream)
    emit("fig16", "kv_raw_exp_planes_mean_ratio",
         float(np.mean(r_raw[7:15])), "x")
    emit("fig16", "kv_trace_exp_planes_mean_ratio",
         float(np.mean(r_tr[7:15])), "x",
         "delta-transformed exponent planes compress far better")
    assert np.mean(r_tr[7:15]) > np.mean(r_raw[7:15])


if __name__ == "__main__":
    run()
