"""Commodity lossless codecs used by the device model (paper §III-B).

TRACE deliberately reuses *generic* codecs — the gain comes from changing
the codec input (plane streams instead of mixed-field words), not from a
bespoke compressor.  We model the paper's two codecs:

* ``lz4`` — a from-scratch LZ4 *block format* encoder/decoder (the offline
  environment has no lz4 binding).  The match search runs as one array
  program over a whole slab of streams (``kernels.lz4.match_events_slab``:
  pallas on a TPU, vectorized numpy elsewhere) and :func:`lz4_emit_events`
  serializes its events to standard LZ4 blocks; this stands in for the
  32-lane streaming LZ4 engine of the controller (paper §IV-E).
* ``zstd`` — the real Zstandard via the ``zstandard`` package.

:func:`compress_slab` is the one compress entry point.  It takes a flush
group's streams as (start, end) ranges of one flat byte slab and applies
block-level *bypass*: when a block is incompressible the device stores it
raw and marks the index entry (paper §III-D "Bypass and correctness
invariants").  The bypass decision is two-stage: a cheap sampled entropy
pre-screen routes near-certainly incompressible blocks to raw storage
*before* paying for compression (the controller's line-rate engines do
the same to avoid stalling on high-entropy planes), and blocks that do
run the codec fall back to raw when the payload fails
:data:`BYPASS_THRESHOLD`.

Contract: every compressed payload round-trips byte-exactly through
:func:`decompress_batch`, and a ``RAW`` flag always means the stored
payload IS the input bytes.  Callers (the layout strategies in
``core.tier``) rely on both.  The per-block scalar encoder that the
stored bytes are held to lives with the tests (``tests/encode_ref.py``).
"""

from __future__ import annotations

import warnings
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from .. import obs

try:
    import zstandard as _zstd

    _ZSTD_C = _zstd.ZstdCompressor(level=3)
    _ZSTD_D = _zstd.ZstdDecompressor()
except (ImportError, AttributeError):  # pragma: no cover - optional dep
    _zstd = None

HAVE_ZSTD = _zstd is not None

# LZ4 match policy, shared by the kernel (asserted in _lz4_kernel) and
# the tests' scalar reference encoder.
_HASH_LOG = 13
_MIN_MATCH = 4
_MFLIMIT = 12          # match must not start within last 12 bytes
_LAST_LITERALS = 5     # last 5 bytes are always literals
_RUN_STRIDE = 4        # interior byte-run positions keep a candidate only
                       # every _RUN_STRIDE bytes: candidates stay ~N/4 on
                       # zero-heavy planes while a match ending mid-run
                       # re-anchors within at most 3 literal bytes


class CorruptPayloadError(ValueError):
    """A compressed payload failed structural validation during decode.

    Raised by :func:`lz4_decompress` for truncated frames, match offsets
    pointing before the produced-length frontier, zero offsets, and
    outputs exceeding the caller's bound — instead of surfacing a raw
    ``IndexError`` or silently wrapping a bad copy.  Subclasses
    ``ValueError`` so existing callers that guard broadly keep working.
    """


# ---------------------------------------------------------------------------
# LZ4 block format
# ---------------------------------------------------------------------------

def _lz4_kernel():
    """The ``kernels.lz4`` match engine.

    The kernel and this module each carry the LZ4 policy constants; a
    drift would silently break kernel-vs-reference byte identity, so it
    is asserted at dispatch.
    """
    from ..kernels import lz4 as _k

    if (_k.HASH_LOG, _k.MIN_MATCH, _k.MFLIMIT, _k.LAST_LITERALS,
            _k.RUN_STRIDE) != (_HASH_LOG, _MIN_MATCH, _MFLIMIT,
                               _LAST_LITERALS, _RUN_STRIDE):
        raise RuntimeError(
            "kernels.lz4 match-policy constants diverged from "
            "core.codec's")
    return _k


def lz4_emit_events(buf: np.ndarray, starts: np.ndarray, ends: np.ndarray,
                    pos: np.ndarray, dist: np.ndarray,
                    mlen: np.ndarray) -> List[bytes]:
    """Serialize a match event tensor to LZ4 block payloads — vectorized.

    The event tensor is the kernel/emit interface: three int64 arrays
    sorted by global ``pos`` (match start), ``dist`` (backwards offset,
    1..65535) and ``mlen`` (true LCP length ≥ 4), one row per selected
    match across ALL streams of the slab; stream membership is implied
    by position.  Gaps between events are literal runs; each stream ends
    in a literal-only closer sequence (the standard end-of-block rule).

    Instead of walking sequences in python, the serializer builds a
    per-sequence table (token value, extension-chain lengths, literal
    source/length, output offset via one cumsum) and scatters token
    bytes, 255-extension chains, literals and offset words with ragged
    numpy fills — O(output bytes) C-speed work, no per-match python.
    """
    S = int(starts.size)
    E = int(pos.size)
    sizes = ends - starts
    sid_e = np.searchsorted(ends, pos, side="right")
    ec = np.bincount(sid_e, minlength=S) if E else np.zeros(S, np.int64)
    ecum = np.concatenate(([0], np.cumsum(ec)))
    mend = pos + mlen
    # literal anchors: stream start for a stream's first event, previous
    # match end for the rest; closer rows start at the last match end
    first = np.ones(E, dtype=bool)
    first[1:] = sid_e[1:] != sid_e[:-1]
    anchor = np.empty(E, dtype=np.int64)
    anchor[first] = starts[sid_e[first]]
    anchor[~first] = mend[:-1][~first[1:]]
    fin_anchor = starts.copy()
    nz = ec > 0
    fin_anchor[nz] = mend[ecum[1:][nz] - 1]

    # sequence table: per stream, ec rows then one literal-only closer
    row_start = np.concatenate(([0], np.cumsum(ec + 1)))
    R = int(row_start[-1])
    ev_rows = row_start[sid_e] + (np.arange(E) - ecum[sid_e])
    fin_rows = row_start[:-1] + ec
    lit = np.empty(R, dtype=np.int64)
    lsrc = np.empty(R, dtype=np.int64)
    mr = np.zeros(R, dtype=np.int64)
    dr = np.zeros(R, dtype=np.int64)
    lit[ev_rows] = pos - anchor
    lsrc[ev_rows] = anchor
    mr[ev_rows] = mlen
    dr[ev_rows] = dist
    lit[fin_rows] = ends - fin_anchor
    lsrc[fin_rows] = fin_anchor

    has_m = mr > 0
    lit_ext = np.where(lit >= 15, (lit - 15) // 255 + 1, 0)
    mx = np.where(has_m, mr - _MIN_MATCH, 0)
    m_ext = np.where(mx >= 15, (mx - 15) // 255 + 1, 0)
    row_len = 1 + lit_ext + lit + 2 * has_m + m_ext
    out_off = np.concatenate(([0], np.cumsum(row_len)))
    out = np.empty(int(out_off[-1]), dtype=np.uint8)

    tok = (np.minimum(lit, 15) << 4) | np.minimum(mx, 15)
    out[out_off[:-1]] = tok.astype(np.uint8)

    def _ext_chain(rows: np.ndarray, base: np.ndarray, val: np.ndarray,
                   cnt: np.ndarray) -> None:
        # 255-extension chain: cnt bytes of 255...255, rem — rem < 255 by
        # construction of cnt = (val - 15) // 255 + 1
        tot = int(cnt.sum())
        gend = np.cumsum(cnt)
        within = np.arange(tot) - np.repeat(gend - cnt, cnt)
        vals = np.full(tot, 255, dtype=np.uint8)
        vals[gend - 1] = (val - 15 - 255 * (cnt - 1)).astype(np.uint8)
        out[np.repeat(base, cnt) + within] = vals

    er = np.flatnonzero(lit_ext > 0)
    if er.size:
        _ext_chain(er, out_off[:-1][er] + 1, lit[er], lit_ext[er])
    lstart = out_off[:-1] + 1 + lit_ext
    lr = np.flatnonzero(lit > 0)
    if lr.size:
        # int32 ragged indices: the literal copy touches ~every output
        # byte, so halving index-array traffic is a measurable win
        cnt = lit[lr]
        within = np.arange(int(cnt.sum()), dtype=np.int32)
        within -= np.repeat((np.cumsum(cnt) - cnt).astype(np.int32), cnt)
        dsti = np.repeat(lstart[lr].astype(np.int32), cnt)
        dsti += within
        srci = np.repeat(lsrc[lr].astype(np.int32), cnt)
        srci += within
        out[dsti] = buf[srci]
    mstart = lstart + lit
    mrows = np.flatnonzero(has_m)
    if mrows.size:
        out[mstart[mrows]] = (dr[mrows] & 0xFF).astype(np.uint8)
        out[mstart[mrows] + 1] = (dr[mrows] >> 8).astype(np.uint8)
    xr = np.flatnonzero(m_ext > 0)
    if xr.size:
        _ext_chain(xr, mstart[xr] + 2, mx[xr], m_ext[xr])

    blob = out.tobytes()
    so = out_off[row_start]
    return [blob[so[s]: so[s + 1]] for s in range(S)]


def _lz4_slab_streams(buf: np.ndarray, starts: np.ndarray,
                      ends: np.ndarray,
                      force: Optional[str] = None) -> List[bytes]:
    """LZ4-compress the addressed streams of a uint8 slab: the match
    kernel's events, serialized by :func:`lz4_emit_events`."""
    starts = np.asarray(starts, dtype=np.int64).ravel()
    ends = np.asarray(ends, dtype=np.int64).ravel()
    gapped = bool(starts.size) and (
        int(starts[0]) != 0 or int(ends[-1]) != buf.size
        or bool((starts[1:] != ends[:-1]).any()))
    if gapped:
        # bypassed streams leave gaps in the slab, and the match
        # kernel's prep passes scale with every slab byte — compact it
        # down to the covered ranges
        sizes = ends - starts
        buf = np.concatenate([buf[a:b] for a, b in zip(starts, ends)])
        ends = np.cumsum(sizes)
        starts = ends - sizes
    with obs.span("tier.lz4_match"):
        pos, dist, mlen = _lz4_kernel().match_events_slab(buf, starts, ends,
                                                          force=force)
    with obs.span("tier.lz4_emit"):
        return lz4_emit_events(buf, starts, ends, pos, dist, mlen)


def lz4_decompress(comp: bytes, max_out: int | None = None) -> bytes:
    """Decode an LZ4 block payload, validating structure as it goes.

    Corrupt frames — truncated extension chains or literal runs, a match
    offset of zero or pointing before the produced-length frontier, or
    output exceeding ``max_out`` — raise :class:`CorruptPayloadError`
    rather than an ``IndexError`` or a silently-wrapped copy.
    """
    out = bytearray()
    i, n = 0, len(comp)
    while i < n:
        token = comp[i]
        i += 1
        lit_len = token >> 4
        if lit_len == 15:
            while True:
                if i >= n:
                    raise CorruptPayloadError(
                        "lz4: truncated literal-length extension at byte "
                        f"{i} of {n}")
                b = comp[i]
                i += 1
                lit_len += b
                if b != 255:
                    break
        if i + lit_len > n:
            raise CorruptPayloadError(
                f"lz4: literal run of {lit_len} overruns frame "
                f"({n - i} bytes left)")
        out.extend(comp[i : i + lit_len])
        i += lit_len
        if max_out is not None and len(out) > max_out:
            # checked before the last-sequence break: a tail literal run
            # must not overshoot the caller's bound either
            raise CorruptPayloadError(
                f"lz4: decompressed size {len(out)} exceeds bound {max_out}")
        if i >= n:
            break  # last sequence has no match part
        if i + 2 > n:
            raise CorruptPayloadError(
                f"lz4: truncated match offset at byte {i} of {n}")
        offset = comp[i] | (comp[i + 1] << 8)
        i += 2
        if offset == 0 or offset > len(out):
            raise CorruptPayloadError(
                f"lz4: match offset {offset} outside produced frontier "
                f"({len(out)} bytes)")
        mlen = (token & 0xF) + _MIN_MATCH
        if (token & 0xF) == 15:
            while True:
                if i >= n:
                    raise CorruptPayloadError(
                        "lz4: truncated match-length extension at byte "
                        f"{i} of {n}")
                b = comp[i]
                i += 1
                mlen += b
                if b != 255:
                    break
        start = len(out) - offset
        if offset >= mlen:
            # disjoint source range — one slice copy
            out += out[start : start + mlen]
        else:
            # overlapping match = repeating pattern of period `offset`
            # (offset 1 is a byte run — the common case on zero-heavy
            # planes); replicate at C speed instead of a python loop
            pattern = bytes(out[start:])
            out += (pattern * (mlen // offset + 1))[:mlen]
        if max_out is not None and len(out) > max_out:
            raise CorruptPayloadError(
                f"lz4: decompressed size {len(out)} exceeds bound {max_out}")
    return bytes(out)


# ---------------------------------------------------------------------------
# zstd wrappers
# ---------------------------------------------------------------------------

def zstd_compress(data: bytes) -> bytes:
    if _zstd is None:  # pragma: no cover
        raise RuntimeError("zstandard not available")
    return _ZSTD_C.compress(data)


def zstd_compress_batch(chunks: Sequence[bytes]) -> List[bytes]:
    """Multi-frame zstd: one library call for a whole flush group.

    ``multi_compress_to_buffer`` produces the same independent frames as
    per-chunk :func:`zstd_compress` calls (same compressor parameters),
    amortizing python→C transitions; falls back to the per-chunk loop on
    older ``zstandard`` builds.
    """
    if _zstd is None:  # pragma: no cover
        raise RuntimeError("zstandard not available")
    if chunks and hasattr(_ZSTD_C, "multi_compress_to_buffer"):
        try:
            res = _ZSTD_C.multi_compress_to_buffer(list(chunks))
            return [res[i].tobytes() for i in range(len(res))]
        # tracecheck: allow-broad-except(multi_compress raises build-specific types; falls back to the byte-identical per-chunk loop)
        except Exception:  # pragma: no cover - library/build specific
            pass
    return [_ZSTD_C.compress(c) for c in chunks]


def zstd_decompress(data: bytes, max_out: int | None = None) -> bytes:
    if _zstd is None:  # pragma: no cover
        raise RuntimeError("zstandard not available")
    return _ZSTD_D.decompress(data, max_output_size=max_out or 0)


def _raw_streams(buf: np.ndarray, starts: np.ndarray, ends: np.ndarray,
                 force: Optional[str] = None) -> List[bytes]:
    return [buf[s:e].tobytes() for s, e in zip(starts, ends)]


def _zstd_streams(buf: np.ndarray, starts: np.ndarray, ends: np.ndarray,
                  force: Optional[str] = None) -> List[bytes]:
    return zstd_compress_batch(_raw_streams(buf, starts, ends))


# ---------------------------------------------------------------------------
# Registry + slab API with bypass
# ---------------------------------------------------------------------------

# name -> (slab compressor, decompressor).  A slab compressor maps
# ``(buf, starts, ends, force)`` to one payload per addressed stream;
# ``force`` pins the LZ4 match kernel's path and is ignored by the rest.
CODECS: Dict[str, Tuple[Callable[..., List[bytes]], Callable[..., bytes]]] = {
    "lz4": (_lz4_slab_streams, lz4_decompress),
    "none": (_raw_streams, lambda b, max_out=None: b),
}
if HAVE_ZSTD:
    CODECS["zstd"] = (_zstd_streams, zstd_decompress)

_warned_fallback = False


def resolve_codec(name: str) -> str:
    """Map a requested codec to an available one.

    ``zstd`` silently degrades to ``lz4`` (with a one-time warning) when the
    ``zstandard`` package is missing, so device models stay usable in minimal
    environments; tests that depend on zstd-specific ratios should check
    :data:`HAVE_ZSTD` and skip instead.
    """
    global _warned_fallback
    if name in CODECS:
        return name
    if name == "zstd":
        if not _warned_fallback:
            warnings.warn(
                "zstandard is not installed; falling back to the built-in "
                "lz4 codec for all 'zstd' devices",
                RuntimeWarning,
                stacklevel=2,
            )
            _warned_fallback = True
        return "lz4"
    raise KeyError(f"unknown codec {name!r}; registered: {sorted(CODECS)}")

RAW, COMPRESSED = 0, 1

# Bypass rule (paper §III-D): a compressed payload is stored only when
# len(comp) < BYPASS_THRESHOLD * len(raw); otherwise the block is stored
# raw and the index entry is flagged.  1.0 = "store compressed iff it is
# strictly smaller" — the conservative setting that can never expand a
# block.  Devices that want headroom for decompression latency can lower
# it (e.g. 0.9 requires a 10% gain before paying the codec on reads).
BYPASS_THRESHOLD = 1.0

# Entropy pre-screen: blocks whose sampled byte distribution is this close
# to uniform (bits/byte, Miller-Madow bias-corrected) AND show no repeated
# 4-byte word among the sampled positions are routed to bypass WITHOUT
# running the codec.  Calibrated so uniform-random payloads ≥ 128 B (e.g.
# mantissa/sign plane streams of well-scaled bf16 tensors, H ≈ 7.6-8.0)
# bypass, while everything LZ4/zstd actually shrinks — periodic patterns,
# text, exponent planes — stays well below (H ≤ 6.8 or duplicate words).
BYPASS_ENTROPY_BITS = 7.5
_PRESCREEN_MIN_LEN = 128     # below this, codec overhead is negligible
_PRESCREEN_BYTES = 1024      # max bytes sampled for the histogram
_PRESCREEN_WORDS = 64        # 4-byte words sampled for the repeat check


def _prescreen_group(rows: np.ndarray) -> np.ndarray:
    """Vectorized pre-screen over a ``(R, n)`` uint8 matrix of same-length
    blocks → boolean bypass decision per row.

    Deterministic (stride sampling, no RNG) and independent per row.
    False negatives only cost a wasted compression attempt; false
    positives would change stored bytes, so both statistics are
    thresholded conservatively.  The tests' per-block reference calls
    this with ``R = 1``, so the two cannot diverge on a threshold.
    """
    R, n = rows.shape
    sample = rows[:, :: max(1, n // _PRESCREEN_BYTES)][:, :_PRESCREEN_BYTES]
    S = sample.shape[1]
    # per-row histograms via one offset bincount
    offs = (np.arange(R, dtype=np.int64) * 256)[:, None]
    counts = np.bincount(
        (sample.astype(np.int64) + offs).ravel(), minlength=256 * R
    ).reshape(R, 256)
    p = counts / S
    with np.errstate(divide="ignore", invalid="ignore"):
        plogp = np.where(counts > 0, p * np.log2(np.where(counts > 0, p, 1.0)),
                         0.0)
    # Miller-Madow correction removes the small-sample bias that would
    # otherwise make uniform data look ~0.3-0.7 bits "compressible".
    entropy = -plogp.sum(axis=1) \
        + ((counts > 0).sum(axis=1) - 1) / (2 * S * np.log(2))
    out = entropy >= BYPASS_ENTROPY_BITS
    if out.any():
        # Long-range repeats hide from a histogram: sample 4-byte words on
        # an even stride; any duplicate means LZ matches are likely —
        # compress instead of bypassing.
        k = min(_PRESCREEN_WORDS, n // 4)
        pos = np.arange(k, dtype=np.int64) * ((n - 4) // max(k - 1, 1))
        words = (
            rows[:, pos].astype(np.uint32)
            | (rows[:, pos + 1].astype(np.uint32) << 8)
            | (rows[:, pos + 2].astype(np.uint32) << 16)
            | (rows[:, pos + 3].astype(np.uint32) << 24)
        )
        sw = np.sort(words, axis=1)
        out &= ~(sw[:, 1:] == sw[:, :-1]).any(axis=1)
    return out


def _prescreen_slab(buf: np.ndarray, starts: np.ndarray,
                    ends: np.ndarray) -> np.ndarray:
    """Per-stream bypass decisions for slab-addressed streams: streams of
    at least ``_PRESCREEN_MIN_LEN`` bytes gather, by length, into one
    ``(R, n)`` matrix for :func:`_prescreen_group`; shorter ones always
    run the codec."""
    sizes = ends - starts
    res = np.zeros(starts.size, dtype=bool)
    for n in np.unique(sizes):
        if n < _PRESCREEN_MIN_LEN:
            continue
        idxs = np.flatnonzero(sizes == n)
        rows = buf[starts[idxs][:, None] + np.arange(int(n))[None, :]]
        res[idxs] = _prescreen_group(rows)
    return res


def compress_slab(slab, starts: Sequence[int], ends: Sequence[int],
                  codec: str,
                  force: Optional[str] = None) -> Tuple[List[bytes], List[int]]:
    """Compress a flush group's streams, addressed INSIDE a flat slab.

    ``slab`` is a flat uint8 buffer, e.g. the ravelled planes of
    ``pack_planes_slab``; ``starts[i]:ends[i]`` bounds stream ``i``.
    Returns one ``(payload, flag)`` per stream, as two lists, with flag ∈
    {RAW, COMPRESSED}.  The pre-screen routes incompressible streams to
    raw storage first; the surviving streams go to the codec's slab
    compressor in one call (LZ4: the match kernel over (start, end)
    bounds, no per-stream bytes materialized), and a payload that fails
    :data:`BYPASS_THRESHOLD` is stored raw.  ``force`` pins the LZ4
    match kernel's path (``"numpy"`` / ``"device"``).
    """
    name = resolve_codec(codec)
    starts = np.asarray(starts, dtype=np.int64).ravel()
    ends = np.asarray(ends, dtype=np.int64).ravel()
    S = int(starts.size)
    buf = np.asarray(slab, dtype=np.uint8).ravel()
    payloads: List[bytes] = [b""] * S
    flags: List[int] = [RAW] * S
    todo: List[int] = []
    with obs.span("tier.prescreen"):
        skips = _prescreen_slab(buf, starts, ends)
    for i, skip in enumerate(skips):
        if skip:
            payloads[i] = buf[starts[i]: ends[i]].tobytes()
        else:
            todo.append(i)
    if todo:
        tsel = np.asarray(todo, dtype=np.int64)
        compress, _ = CODECS[name]
        comps = compress(buf, starts[tsel], ends[tsel], force)
        for i, comp in zip(todo, comps):
            n = int(ends[i] - starts[i])
            if len(comp) >= BYPASS_THRESHOLD * n:
                payloads[i] = buf[starts[i]: ends[i]].tobytes()
            else:
                payloads[i], flags[i] = comp, COMPRESSED
    return payloads, flags


def decompress_block(payload: bytes, flag: int, codec: str, orig_len: int) -> bytes:
    if flag == RAW:
        return payload
    _, d = CODECS[resolve_codec(codec)]
    out = d(payload, max_out=orig_len)
    return out


def decompress_batch(payloads: Sequence[bytes], flags: Sequence[int],
                     codec: str, orig_lens: Sequence[int]) -> List[bytes]:
    """Inverse of :func:`compress_slab`: one codec resolve for the group."""
    _, d = CODECS[resolve_codec(codec)]
    return [
        pay if fl == RAW else d(pay, max_out=n)
        for pay, fl, n in zip(payloads, flags, orig_lens)
    ]


def ratio(orig: int, comp: int) -> float:
    """Compression ratio S_orig / S_comp (≥ 1 is a gain)."""
    return orig / max(comp, 1)
