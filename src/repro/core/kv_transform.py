"""KV-specific transform — Mechanism I (paper §III-B, Fig. 8).

The host writes KV token-major; adjacent addresses hold *different*
channels, whose scales differ, so the byte stream is high-entropy.  TRACE
buffers a window of ``n`` tokens, transposes to channel-major groups
``G_j = {k_{t,j}}`` (Eq. 3), then de-correlates each group by replacing the
exponent field with a small delta against a per-channel base exponent
``beta_j`` (Eq. 5) before bit-plane packing.

Losslessness.  The paper's delta can be negative; we make the transform
unconditionally invertible by computing the delta mod 256 and *zigzag*
encoding it around zero (small |delta| → small code → zero runs in the
high-order delta planes, which is exactly what the codec exploits).
``beta_j`` is the modal exponent of the channel group, stored as
constant-size per-stream metadata (paper §III-D "Metadata management").

The transformed element keeps the BF16 container layout:
    bit 15   sign            (unchanged)
    bits14..7 zigzag(exp - beta_j)
    bits 6..0 mantissa        (unchanged)
"""

from __future__ import annotations

import dataclasses

import numpy as np
import jax.numpy as jnp

from .bitplane import (
    EXP_BITS,
    EXP_LO,
    MAN_BITS,
    pack_planes,
    unpack_planes,
)

_EXP_MASK = np.uint16(((1 << EXP_BITS) - 1) << EXP_LO)
_REST_MASK = np.uint16(~(((1 << EXP_BITS) - 1) << EXP_LO) & 0xFFFF)


@dataclasses.dataclass
class KVBlockMeta:
    """Constant-size per-block state needed to invert the transform."""

    beta: np.ndarray      # (C,) uint8 — per-channel base exponent
    n_tokens: int
    n_channels: int

    @property
    def nbytes(self) -> int:
        return self.beta.size + 8  # betas + window header


# -- exponent delta (zigzag, mod-256 → always invertible) -------------------

def _zigzag_u8(d: np.ndarray) -> np.ndarray:
    """Map signed int8-range deltas to small unsigned codes: 0,-1,1,-2,… →
    0,1,2,3,…  Input is the mod-256 difference as uint8."""
    s = d.astype(np.int16)
    s = np.where(s >= 128, s - 256, s)  # interpret as signed
    z = np.where(s >= 0, 2 * s, -2 * s - 1)
    return z.astype(np.uint8)


def _unzigzag_u8(z: np.ndarray) -> np.ndarray:
    z = z.astype(np.int16)
    s = np.where(z % 2 == 0, z // 2, -(z + 1) // 2)
    return (s % 256).astype(np.uint8)


def _modal_exponent(exp: np.ndarray) -> np.ndarray:
    """Per-row modal value of (C, n) uint8 exponents."""
    C = exp.shape[0]
    out = np.empty(C, dtype=np.uint8)
    for j in range(C):
        out[j] = np.bincount(exp[j], minlength=256).argmax()
    return out


# -- forward / inverse transform on a (n_tokens, C) block --------------------

def kv_forward(block_u16: np.ndarray) -> tuple[np.ndarray, KVBlockMeta]:
    """Token-major (n, C) uint16 → channel-major transformed flat uint16.

    Returns the transformed element stream (flattened channel-major, i.e.
    all tokens of channel 0, then channel 1, …) and the per-block metadata.
    """
    n, C = block_u16.shape
    cm = np.ascontiguousarray(block_u16.T)          # (C, n) channel-major
    exp = ((cm & _EXP_MASK) >> EXP_LO).astype(np.uint8)
    beta = _modal_exponent(exp)
    delta = (exp.astype(np.int16) - beta[:, None].astype(np.int16)) % 256
    z = _zigzag_u8(delta.astype(np.uint8))
    out = (cm & _REST_MASK) | (z.astype(np.uint16) << EXP_LO)
    return out.ravel(), KVBlockMeta(beta=beta, n_tokens=n, n_channels=C)


def kv_inverse(stream_u16: np.ndarray, meta: KVBlockMeta) -> np.ndarray:
    """Invert :func:`kv_forward` → token-major (n, C) uint16."""
    C, n = meta.n_channels, meta.n_tokens
    cm = stream_u16.reshape(C, n)
    z = ((cm & _EXP_MASK) >> EXP_LO).astype(np.uint8)
    delta = _unzigzag_u8(z)
    exp = (delta.astype(np.int16) + meta.beta[:, None].astype(np.int16)) % 256
    out = (cm & _REST_MASK) | (exp.astype(np.uint16) << EXP_LO)
    return np.ascontiguousarray(out.T)


def kv_forward_batch(windows: np.ndarray) -> tuple[np.ndarray, list]:
    """Vectorized :func:`kv_forward` over same-shape windows.

    ``windows``: ``(B, n, C)`` uint16 token-major.  Returns ``((B, n*C)``
    transformed channel-major streams, ``B`` metas)`` — identical per
    window to the scalar transform (the modal exponent is the same
    bincount-argmax, just computed for all ``B*C`` channel groups in one
    offset-bincount pass).  The write-side mirror of
    :func:`kv_inverse_batch`: a flush group's windows transform in two
    numpy passes instead of one python call per window.
    """
    B, n, C = windows.shape
    cm = np.ascontiguousarray(windows.transpose(0, 2, 1))   # (B, C, n)
    exp = ((cm & _EXP_MASK) >> EXP_LO).astype(np.uint8)
    offs = (np.arange(B * C, dtype=np.int64) * 256)[:, None]
    counts = np.bincount(
        (exp.reshape(B * C, n).astype(np.int64) + offs).ravel(),
        minlength=256 * B * C,
    ).reshape(B * C, 256)
    beta = counts.argmax(axis=1).astype(np.uint8).reshape(B, C)
    delta = (exp.astype(np.int16) - beta[:, :, None].astype(np.int16)) % 256
    z = _zigzag_u8(delta.astype(np.uint8))
    out = (cm & _REST_MASK) | (z.astype(np.uint16) << EXP_LO)
    metas = [KVBlockMeta(beta=beta[b].copy(), n_tokens=n, n_channels=C)
             for b in range(B)]
    return out.reshape(B, n * C), metas


def kv_inverse_batch(streams: np.ndarray, metas: list) -> np.ndarray:
    """Vectorized :func:`kv_inverse` over same-shape windows.

    ``streams``: ``(B, n*C)`` uint16, one transformed window per row;
    ``metas``: B :class:`KVBlockMeta` with identical ``(n_tokens,
    n_channels)``.  Returns token-major ``(B, n, C)`` uint16.  Batched
    device reads use this to invert a whole request batch in two numpy
    passes instead of one python call per 4 KB block.
    """
    C, n = metas[0].n_channels, metas[0].n_tokens
    cm = streams.reshape(len(metas), C, n)
    beta = np.stack([m.beta for m in metas])          # (B, C)
    z = ((cm & _EXP_MASK) >> EXP_LO).astype(np.uint8)
    delta = _unzigzag_u8(z)
    exp = (delta.astype(np.int16) + beta[:, :, None].astype(np.int16)) % 256
    out = (cm & _REST_MASK) | (exp.astype(np.uint16) << EXP_LO)
    return np.ascontiguousarray(out.transpose(0, 2, 1))


def kv_pack(block_u16: np.ndarray) -> tuple[np.ndarray, KVBlockMeta]:
    """Full Mechanism-I chain: transform then bit-plane pack (Fig. 8)."""
    stream, meta = kv_forward(block_u16)
    return pack_planes(stream), meta


def kv_unpack(planes: np.ndarray, meta: KVBlockMeta) -> np.ndarray:
    stream = unpack_planes(planes, meta.n_tokens * meta.n_channels)
    return kv_inverse(stream, meta)


# -- jnp forward: the device transform of kv_pack_planes_slab (modal
# exponents, then the delta), and the oracle for the kv_delta kernel -------

def kv_beta_jnp(windows: jnp.ndarray) -> jnp.ndarray:
    """(B, n, C) uint16 token-major windows → (B, C) uint8 modal exponents.

    The same value as :func:`kv_forward_batch`'s bincount-argmax, ties
    included (the smallest exponent among the most frequent), from a
    pairwise count over each channel's ``n`` tokens — no 256-bin
    histogram, and the channels stay on the minor axis.
    """
    exp = (windows.astype(jnp.int32) >> 7) & 0xFF               # (B, n, C)
    count = jnp.sum(exp[:, :, None, :] == exp[:, None, :, :], axis=2,
                    dtype=jnp.int32)
    best = jnp.max(count * 256 + (255 - exp), axis=1)            # (B, C)
    return (255 - (best & 0xFF)).astype(jnp.uint8)


def kv_forward_jnp(block_u16: jnp.ndarray, beta: jnp.ndarray) -> jnp.ndarray:
    """(n, C) uint16 + (C,) uint8 beta → (C, n) transformed uint16 (jnp)."""
    cm = block_u16.T.astype(jnp.uint16)
    exp = ((cm & jnp.uint16(0x7F80)) >> 7).astype(jnp.int16)
    d = (exp - beta[:, None].astype(jnp.int16)) % 256
    s = jnp.where(d >= 128, d - 256, d)
    z = jnp.where(s >= 0, 2 * s, -2 * s - 1).astype(jnp.uint16)
    return (cm & jnp.uint16(0x807F)) | (z << jnp.uint16(7))
