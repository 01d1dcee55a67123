"""PNM page-scoring kernel: device-side top-k candidate ranking.

The processing-near-memory read mode (``core.tier.GatherReq``) scores
every spilled KV page against a host-supplied query digest ON the
device, then ships full precision for only the top-k winners.  Scoring
runs on a *plane subset* — the gather's ``score_view`` defaults to
``MAN0`` (sign + full exponent + one guard mantissa plane), so the
score fetch touches a fraction of each page's stored planes — and this
module turns those reduced-precision rows into one float32 score per
page:

    score(page) = max over valid token rows t of  <row_t, digest>

(the max-dot proxy for the page's attention mass against the digest —
the dynamic-placement literature's top-k page selection signal).

Twin implementations, mirroring ``kernels/lz4.py`` / ``bitplane.py``:

* ``page_scores_pallas`` — a pallas kernel (one grid step per block of
  8 pages; the masked dot+max reduction stays in VMEM), compiled on a
  TPU and run in interpret mode for the CPU parity tests;
* the vectorized-numpy twin inside :func:`page_scores` — the CPU
  production path the tier device calls.

The two are byte-identical (see :func:`page_scores`), so a gather picks
the same winners on either backend.

Determinism: winner selection must be bit-stable across sync/async
submission and shard counts, so :func:`topk_select` ranks by
(-score, candidate position) — equal scores break toward the earlier
candidate in the host-chosen key order, never by float reduction
accident.  The tie-break is exercised by the determinism tests with
byte-identical duplicate pages.
"""

from __future__ import annotations

import functools
from typing import List, Sequence

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import dispatch


def u16_rows_to_f32(u16: np.ndarray, channels: int) -> np.ndarray:
    """Reinterpret a device payload (uint16 bf16 bit patterns) as
    ``(tokens, channels)`` float32 rows for scoring."""
    flat = np.ascontiguousarray(np.asarray(u16, dtype=np.uint16)).ravel()
    if flat.size % channels:
        raise ValueError(
            f"page of {flat.size} elems does not factor into "
            f"{channels}-channel rows"
        )
    return (flat.view(ml_dtypes.bfloat16)
            .astype(np.float32)
            .reshape(-1, channels))


_PAGE_BLOCK = 8       # pages per grid step: one sublane tile
_LANES = 128


def _tree_sum(terms: list):
    """Sum a power-of-two list by halving: ``t[i] + t[i + n/2]`` per level.
    Both twins add in exactly this order, so their f32 sums agree bit for
    bit."""
    while len(terms) > 1:
        h = len(terms) // 2
        terms = [terms[i] + terms[i + h] for i in range(h)]
    return terms[0]


def _score_kernel(digest_ref, valid_ref, page_ref, out_ref):
    """One grid step scores ``_PAGE_BLOCK`` pages: per-channel products,
    halving-tree sum over channels, masked max over token rows.

    digest (Cp,) f32 in SMEM; valid (PB, 1) i32; pages (Cp, PB, T) f32,
    channel-major so every term is a whole (PB, T) tile; out (PB, 128)
    with the score broadcast along lanes."""
    dots = _tree_sum([page_ref[c] * digest_ref[c]
                      for c in range(page_ref.shape[0])])      # (PB, T)
    t_ix = jax.lax.broadcasted_iota(jnp.int32, dots.shape, 1)
    best = jnp.max(jnp.where(t_ix < valid_ref[...], dots, -jnp.inf),
                   axis=1, keepdims=True)
    out_ref[...] = jnp.broadcast_to(best, out_ref.shape)


@functools.partial(jax.jit, static_argnames=("interpret",))
def page_scores_pallas(padded: jnp.ndarray, valid: jnp.ndarray,
                       digest: jnp.ndarray,
                       interpret: bool = True) -> jnp.ndarray:
    """(P, T, C) f32 pages + (P,) valid lens + (C,) digest → (P,) f32.

    Channels are zero-padded to a power of two and pages to a multiple of
    ``_PAGE_BLOCK``; the numpy twin pads channels the same way."""
    P, T, C = padded.shape
    cp = dispatch.bucket(C)
    pp = -(-P // _PAGE_BLOCK) * _PAGE_BLOCK
    pages = jnp.pad(padded.astype(jnp.float32),
                    ((0, pp - P), (0, 0), (0, cp - C))).transpose(2, 0, 1)
    out = pl.pallas_call(
        _score_kernel,
        grid=(pp // _PAGE_BLOCK,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((_PAGE_BLOCK, 1), lambda i: (i, 0)),
            pl.BlockSpec((cp, _PAGE_BLOCK, T), lambda i: (0, i, 0)),
        ],
        out_specs=pl.BlockSpec((_PAGE_BLOCK, _LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((pp, _LANES), jnp.float32),
        interpret=interpret,
    )(jnp.pad(digest.astype(jnp.float32), (0, cp - C)),
      jnp.pad(valid.astype(jnp.int32), (0, pp - P)).reshape(pp, 1),
      pages)
    return out[:P, 0]


def page_scores(padded: np.ndarray, valid: np.ndarray, digest: np.ndarray,
                force: str | None = None) -> np.ndarray:
    """Score a padded page stack: ``(P, T, C)`` f32 rows (rows past
    ``valid[p]`` ignored) against a ``(C,)`` digest → ``(P,)`` f32.

    The digest is rounded to bf16 first.  Page rows are bf16 values, so
    every row·digest product is then exact in f32, and with the shared
    halving-tree channel sum the device kernel and the numpy twin return
    identical bytes.  Pages with zero valid rows score ``-inf`` (they rank
    last, ties by candidate position).  ``force``: ``"numpy"`` pins the
    twin, ``"pallas"`` pins the kernel (interpret mode off the TPU).
    """
    padded = np.asarray(padded, dtype=np.float32)
    valid = np.asarray(valid, dtype=np.int64)
    digest = (np.asarray(digest, dtype=np.float32)
              .astype(ml_dtypes.bfloat16).astype(np.float32))
    P, T, C = padded.shape
    if P == 0 or T == 0:
        return np.full((P,), -np.inf, dtype=np.float32)
    path = dispatch.choose("score", force)
    if path != dispatch.NUMPY:
        # bucket the page count so a serve run compiles few shapes
        pp = max(_PAGE_BLOCK, dispatch.bucket(P))
        pages = np.zeros((pp, T, C), dtype=np.float32)
        pages[:P] = padded
        lens = np.zeros(pp, dtype=np.int32)
        lens[:P] = valid
        out = page_scores_pallas(
            jnp.asarray(pages), jnp.asarray(lens), jnp.asarray(digest),
            interpret=path == dispatch.INTERPRET,
        )
        return np.asarray(out, dtype=np.float32)[:P]
    cp = dispatch.bucket(C)
    prod = np.zeros((P, T, cp), dtype=np.float32)
    prod[..., :C] = padded * digest
    dots = _tree_sum([prod[..., c] for c in range(cp)])     # (P, T)
    mask = np.arange(T)[None, :] < valid[:, None]
    return np.where(mask, dots, -np.inf).max(axis=1).astype(np.float32)


def page_scores_u16(pages: Sequence[np.ndarray], digest: np.ndarray,
                    force: str | None = None) -> np.ndarray:
    """Score raw device payloads: each page is a uint16 (bf16-pattern)
    array whose elements factor into ``digest.size``-channel rows.
    Ragged pages are padded to the longest and masked."""
    digest = np.asarray(digest, dtype=np.float32)
    if not pages:
        return np.zeros((0,), dtype=np.float32)
    rows = [u16_rows_to_f32(p, digest.size) for p in pages]
    valid = np.array([r.shape[0] for r in rows], dtype=np.int64)
    T = max(1, int(valid.max()))
    padded = np.zeros((len(rows), T, digest.size), dtype=np.float32)
    for i, r in enumerate(rows):
        padded[i, : r.shape[0]] = r
    return page_scores(padded, valid, digest, force=force)


def topk_select(scores: np.ndarray, k: int) -> List[int]:
    """Deterministic top-k: descending score, ties broken by candidate
    position (stable across shard counts and sync/async paths).  ``k``
    past the candidate count clamps; ``k=0`` selects nothing."""
    scores = np.asarray(scores)
    n = scores.size
    if n == 0 or k <= 0:
        return []
    order = np.lexsort((np.arange(n), -scores))
    return [int(i) for i in order[: min(k, n)]]
