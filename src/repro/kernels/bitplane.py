"""Pallas TPU kernels: bit-plane pack / elastic unpack (paper §III-A/C).

TPU adaptation (DESIGN.md §2): the paper's transpose runs in a CXL
controller; on a TPU system the bit-plane layout lives in the offload
tier / HBM and the transpose+reconstruction run on-chip, next to the
consumer.  These kernels stream (R, C) uint16 tiles through VMEM:

* ``pack_kernel``    — (R, C) u16 → (16, R, C//8) u8 plane stack.  The
  wrapper lays the input out as (8, R, C//8) (byte-member-major); one
  grid step owns a (Br, C) row stripe and produces all 16 output planes
  of it in-register.
* ``unpack_kernel``  — inverse, with *elastic* plane masking + guard-plane
  round-to-nearest-even fused in (Eq. 6/7): unfetched planes are never
  read (their BlockSpec rows are masked out by zeroing — on real TPU the
  fetched-plane subset is sliced by the caller, so HBM→VMEM bytes scale
  with the view; see ops.elastic_unpack).

Block shapes: C is kept whole per grid step (plane bytes stay contiguous
along the minor axis — lane-dim friendly, multiples of 128 bytes when
C ≥ 1024); Br rows per step bound VMEM: Br·C·2 B in + 16·Br·C/8 B out =
4·Br·C bytes = 256 KiB at the encode slab's (64, 1024).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from ..core.bitplane import BF16_BITS
from . import dispatch

DEFAULT_BLOCK_R = 64
_PACK_C = 1024             # slab row width: C // 8 = 128 plane bytes
_PACK_MIN_ELEMS = 8 * _PACK_C


def _pack_kernel(x_ref, out_ref):
    """x: (8, Br, C//8) u16 with ``x[j, r, k]`` = element ``8k + j`` of row
    ``r`` → out: (16, Br, C//8) u8, MSB-first within each byte.

    The wrapper moves the 8 elements of an output byte onto the leading
    axis, so the kernel is pure elementwise work on (Br, C//8) tiles:
    splitting the lane dim in-kernel is a reshape the TPU compiler
    refuses."""
    x = x_ref[...].astype(jnp.int32)
    shifts = jax.lax.broadcasted_iota(jnp.int32, (BF16_BITS, 1, 1), 0)
    acc = ((x[0][None] >> shifts) & 1) << 7
    for j in range(1, 8):
        acc |= ((x[j][None] >> shifts) & 1) << (7 - j)
    out_ref[...] = acc.astype(jnp.uint8)


def _unpack_kernel(planes_ref, out_ref, *, keep_mask: int, cut: int,
                   do_round: bool):
    """planes: (16, Br, C//8) u8 → out: (Br, C) u16, masked + rounded.

    ``keep_mask``/``cut``/``do_round`` are compile-time view constants —
    the alias decides the planes, never per-element values (paper §III-C).
    """
    p = planes_ref[...].astype(jnp.int32)
    nb, br, c8 = p.shape
    # unpack bytes → bits along the minor axis (MSB-first)
    shifts_in = 7 - jax.lax.broadcasted_iota(jnp.int32, (1, 1, 1, 8), 3)
    bits = (p[..., None] >> shifts_in) & 1        # (16, Br, C//8, 8)
    bits = bits.reshape(nb, br, c8 * 8)
    shifts = jax.lax.broadcasted_iota(jnp.int32, (BF16_BITS, 1, 1), 0)
    u = jnp.sum(bits << shifts, axis=0)           # (Br, C) int32 patterns

    if do_round:
        sign = u & 0x8000
        mag = u & 0x7FFF
        is_special = (u & 0x7F80) == 0x7F80
        half = 1 << (cut - 1)
        gmask = (1 << cut) - 1
        guard = mag & gmask
        lsb = (mag >> cut) & 1
        round_up = (guard > half) | ((guard == half) & (lsb == 1))
        mag_r = (mag & ~gmask) + (round_up.astype(jnp.int32) << cut)
        mag_r = jnp.minimum(mag_r, 0x7F80)
        special_out = u & keep_mask
        man = u & 0x7F
        nan_lost = is_special & (man != 0) & ((special_out & 0x7F) == 0)
        special_out = jnp.where(nan_lost, special_out | 0x40, special_out)
        u = jnp.where(is_special, special_out, sign | mag_r)
    out_ref[...] = (u & keep_mask).astype(jnp.uint16)


def pack_planes_slab(flat_u16, force: str | None = None):
    """Pack a flat ``(n,)`` uint16 encode slab to ``(16, n // 8)`` uint8
    planes — the write-side pack primitive of the batched encode pipeline.

    Dispatch (:func:`repro.kernels.dispatch.choose`): on a TPU the slab is
    zero-padded to a power-of-two number of ``_PACK_C``-element rows and
    packed by :func:`pack_planes_pallas`, so every slab takes the kernel
    and the compiled shapes stay few; elsewhere the numpy
    :func:`~repro.core.bitplane.pack_planes` twin runs.  Both produce the
    same bytes — plane streams are element-order packed, so the padding
    only appends bytes past ``n // 8`` that are sliced off.

    ``force``: ``"numpy"`` pins the twin; ``"pallas"`` pins the kernel
    (interpret mode off the TPU — used by the equivalence tests).
    """
    from ..core.bitplane import pack_planes

    flat = np.asarray(flat_u16, dtype=np.uint16).ravel()
    n = flat.size
    if n % 8:
        raise ValueError(f"slab length {n} not a multiple of 8")
    if n == 0:
        return pack_planes(flat)
    path = dispatch.choose("pack", force)
    if path == dispatch.NUMPY:
        return pack_planes(flat)
    npad = max(_PACK_MIN_ELEMS, dispatch.bucket(n))
    rows = npad // _PACK_C
    x = np.zeros(npad, dtype=np.uint16)
    x[:n] = flat
    planes = pack_planes_pallas(
        jnp.asarray(x.reshape(rows, _PACK_C)),
        block_r=min(rows, DEFAULT_BLOCK_R),
        interpret=path == dispatch.INTERPRET,
    )
    planes = np.asarray(planes).reshape(BF16_BITS, npad // 8)
    return np.ascontiguousarray(planes[:, : n // 8])


@functools.partial(jax.jit, static_argnames=("block_r", "interpret"))
def pack_planes_pallas(x_u16: jnp.ndarray, block_r: int = DEFAULT_BLOCK_R,
                       interpret: bool = True) -> jnp.ndarray:
    """(R, C) uint16 → (16, R, C//8) uint8 (C % 8 == 0, R % block_r == 0)."""
    R, C = x_u16.shape
    br = min(block_r, R)
    assert R % br == 0 and C % 8 == 0
    c8 = C // 8
    xt = x_u16.reshape(R, c8, 8).transpose(2, 0, 1)
    return pl.pallas_call(
        _pack_kernel,
        grid=(R // br,),
        in_specs=[pl.BlockSpec((8, br, c8), lambda i: (0, i, 0))],
        out_specs=pl.BlockSpec((BF16_BITS, br, c8), lambda i: (0, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BF16_BITS, R, c8), jnp.uint8),
        interpret=interpret,
        name="pack_planes_pallas",   # the trace's op name, as metrics read it
    )(xt)


def unpack_planes_pallas(planes: jnp.ndarray, *, r_e: int = 8, r_m: int = 7,
                         d_m: int = 0, block_r: int = DEFAULT_BLOCK_R,
                         interpret: bool = True) -> jnp.ndarray:
    """(16, R, C//8) uint8 → (R, C) uint16 at view (r_e, r_m, d_m).

    The full plane stack is accepted; unfetched planes are zeroed before
    the call by ops.elastic_unpack (bytes-scaling happens there — the
    kernel itself is the fused reconstruct).
    """
    _, R, C8 = planes.shape
    br = min(block_r, R)
    assert R % br == 0
    keep = (
        0x8000
        | (((1 << r_e) - 1) << (15 - r_e))
        | (((1 << r_m) - 1) << (7 - r_m))
    )
    cut = 7 - r_m
    do_round = bool(d_m > 0 and r_e == 8 and (r_m, d_m) != (7, 0) and cut > 0)
    kern = functools.partial(
        _unpack_kernel, keep_mask=keep, cut=max(cut, 1), do_round=do_round
    )
    return pl.pallas_call(
        kern,
        grid=(R // br,),
        in_specs=[pl.BlockSpec((BF16_BITS, br, C8), lambda i: (0, i, 0))],
        out_specs=pl.BlockSpec((br, C8 * 8), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((R, C8 * 8), jnp.uint16),
        interpret=interpret,
    )(planes)
