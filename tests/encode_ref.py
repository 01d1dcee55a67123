"""Per-block reference encoder that the tier's write path is held to.

Production compresses through one entry point, ``codec.compress_slab``
(a flush group's streams as ranges of one slab: the LZ4 match kernel,
then a vectorized emit), and each layout encodes a whole flush group in
one ``encode_batch``.  This module restates both one block at a time, in
plain python, so the tests can require equal bytes:

* :func:`lz4_compress` — a sequential LZ4 block-format encoder with the
  repo's match policy (the constants come from ``codec``);
* :func:`prescreen_bypass` and :func:`compress_block` — the bypass rule
  per block, on ``codec``'s pre-screen core and threshold;
* :func:`encode_blocks_ref` — a layout's encode, per window KV
  transform, per block plane pack and per stream codec.

Only tests import it.
"""

from __future__ import annotations

from typing import List, Sequence, Tuple

import numpy as np

from repro.core import codec
from repro.core.bitplane import BF16_BITS, pack_planes
from repro.core.kv_transform import kv_forward

_HASH_SIZE = 1 << codec._HASH_LOG


def _lz4_words_hashes(buf: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """4-byte little-endian words and their hashes at every position."""
    w = (
        buf[:-3].astype(np.uint32)
        | (buf[1:-2].astype(np.uint32) << 8)
        | (buf[2:-1].astype(np.uint32) << 16)
        | (buf[3:].astype(np.uint32) << 24)
    )
    hashes = ((w * np.uint32(2654435761))
              >> np.uint32(32 - codec._HASH_LOG)).astype(np.int64)
    return w, hashes


def _emit_seq(out: bytearray, data: bytes, lit_start: int, lit_end: int,
              mlen: int, dist: int):
    """Append one LZ4 sequence (token, literal run, optional match) with
    its 255-extension chains; ``mlen == 0`` emits the end-of-block
    literal-only sequence."""
    append = out.append
    lit_len = lit_end - lit_start
    tok_lit = min(lit_len, 15)
    tok_match = min(mlen - codec._MIN_MATCH, 15) if mlen else 0
    append((tok_lit << 4) | tok_match)
    rest = lit_len - 15
    while rest >= 0:
        append(min(rest, 255))
        if rest < 255:
            break
        rest -= 255
    out += data[lit_start:lit_end]
    if mlen:
        append(dist & 0xFF)
        append(dist >> 8)
        rest = mlen - codec._MIN_MATCH - 15
        while rest >= 0:
            append(min(rest, 255))
            if rest < 255:
                break
            rest -= 255


def _lz4_emit(data: bytes, events, out: bytearray):
    """Serialize ascending ``(pos, dist, mlen)`` events over ``data``:
    literals between events, then a literal-only closing sequence."""
    anchor = 0
    for pos, dist, mlen in events:
        _emit_seq(out, data, anchor, pos, mlen, dist)
        anchor = pos + mlen
    _emit_seq(out, data, anchor, len(data), 0, 0)


def _lz4_events_scalar(data: bytes) -> list:
    """Greedy match scan over one block, one position at a time.

    Every position feeds a last-occurrence hash table; a position ``i``
    outside any selected match starts a match when its table candidate
    has the same 4-byte word within the 64 KiB window; matches extend by
    LCP, bounded by the end-of-block literal rules.  Offset-1 candidates
    are honoured only at a run's FIRST interior position
    (``data[i-2] != data[i-1]``) and every ``_RUN_STRIDE``-th after it:
    one uncapped match covers the whole run.
    """
    n = len(data)
    events: list = []
    if n < codec._MFLIMIT + 1:
        return events
    w_np, h_np = _lz4_words_hashes(np.frombuffer(data, dtype=np.uint8))
    w, hashes = w_np.tolist(), h_np.tolist()
    table = [-1] * _HASH_SIZE
    limit = n - codec._MFLIMIT
    cur_end = 0
    for i in range(n - 3):
        h = hashes[i]
        cand = table[h]
        table[h] = i
        if (i >= limit or i < cur_end or cand < 0
                or i - cand > 0xFFFF or w[cand] != w[i]):
            continue
        dist = i - cand
        if (dist == 1 and i >= 2 and data[i - 2] == data[i - 1]
                and i % codec._RUN_STRIDE):
            continue          # run interior: covered by run-first / stride
        max_len = n - codec._LAST_LITERALS - i
        mlen = codec._MIN_MATCH
        while (mlen + 32 <= max_len
               and data[cand + mlen : cand + mlen + 32]
               == data[i + mlen : i + mlen + 32]):
            mlen += 32
        while mlen < max_len and data[cand + mlen] == data[i + mlen]:
            mlen += 1
        events.append((i, dist, mlen))
        cur_end = i + mlen
    return events


def lz4_compress(data: bytes) -> bytes:
    """LZ4 block-format compression of one block."""
    if len(data) == 0:
        return b"\x00"
    out = bytearray()
    _lz4_emit(data, _lz4_events_scalar(data), out)
    return bytes(out)


def prescreen_bypass(data: bytes) -> bool:
    """True when ``data`` is near-certainly incompressible: production's
    pre-screen core on one row."""
    if len(data) < codec._PRESCREEN_MIN_LEN:
        return False
    return bool(codec._prescreen_group(
        np.frombuffer(data, dtype=np.uint8).reshape(1, -1))[0])


def compress_block(data: bytes, name: str) -> Tuple[bytes, int]:
    """One block's ``(payload, flag)`` under the bypass rule."""
    if prescreen_bypass(data):
        return data, codec.RAW
    name = codec.resolve_codec(name)
    if name == "lz4":
        comp = lz4_compress(data)
    elif name == "zstd":
        comp = codec.zstd_compress(data)
    else:
        comp = data
    if len(comp) >= codec.BYPASS_THRESHOLD * len(data):
        return data, codec.RAW
    return comp, codec.COMPRESSED


def slab_of(chunks: Sequence[bytes]
            ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """``(slab, starts, ends)`` addressing ``chunks`` back to back."""
    sizes = np.asarray([len(c) for c in chunks], dtype=np.int64)
    ends = np.cumsum(sizes)
    return (np.frombuffer(b"".join(chunks), dtype=np.uint8),
            ends - sizes, ends)


def encode_blocks_ref(layout, chunks, name: str, kv_windows=None
                      ) -> List[tuple]:
    """What ``layout.encode_batch(chunks, name, kv_windows)`` must return,
    built one block at a time."""
    chunks, metas = list(chunks), [None] * len(chunks)
    for i, w in enumerate(kv_windows or ()):
        if w is not None:
            stream, metas[i] = kv_forward(w)
            chunks[i] = np.pad(stream, (0, -stream.size % 8))
    out = []
    for chunk, meta in zip(chunks, metas):
        if layout.plane_aligned:
            planes = pack_planes(chunk.ravel())
            pays, flags = zip(*(compress_block(planes[p].tobytes(), name)
                                for p in range(BF16_BITS)))
            out.append((list(pays), list(flags), meta))
        elif layout.uses_codec:
            pay, flag = compress_block(chunk.tobytes(), name)
            out.append(([pay], [flag], None))
        else:
            out.append(([chunk.tobytes()], [codec.RAW], None))
    return out
