"""Device-resident LZ4 match kernel (paper §IV-E's 32-lane engine).

The encoder's hot path — match-table build, previous-occurrence
resolution, LCP extension and greedy selection — restated as array
programs over a whole flush group's concatenated (plane, block) streams,
so no per-byte python loop runs on the write path.  Four passes:

1. **prep** — 4-byte little-endian words, multiplicative hashes and
   byte-run boundaries for every position.  On a TPU this is a pallas
   kernel (`_prep_kernel`, elementwise over shifted views of
   the slab); elsewhere one vectorized numpy pass.
2. **previous occurrence** — the per-stream last-occurrence hash table,
   for all positions at once: one stable sort of stream-namespaced hash
   keys, then same-key adjacency.  Candidates can never cross a stream
   boundary, exactly like the reference scan's per-block table.
3. **candidate filter** — window / end-of-block / run-stride rules as
   boolean masks (the rules of the tests' per-block reference scan).
4. **greedy select** — every stream keeps a cursor; one round advances
   ALL live streams by their next selected match (LCP resolved lazily:
   run-boundary table for offset-1 byte runs, word-gallop otherwise —
   selected matches never overlap, so total extension work is bounded by
   the slab).  Rounds are vectorized across streams; the loop runs
   max-matches-per-stream times, not once per candidate.

The result is a compact ``(pos, dist, mlen)`` event tensor — selected
matches in stream order.  Only the final byte-level token serialization
(``codec.lz4_emit_events``) stays host-side.

Dispatch goes through ``kernels.dispatch.choose``, like
``bitplane.pack_planes_slab``: the device path (pallas prep + jnp passes
under one jit) runs on a TPU or under ``force="device"`` (interpret-mode
pallas off the TPU — the equivalence tests); the numpy path runs
anywhere else and is the CPU production encoder.  Both are
byte-identical to the scalar per-block reference encoder that the tests
keep (``tests/encode_ref.py``).
"""

from __future__ import annotations

import functools
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl

from . import dispatch

# LZ4 block-format constants + repo match-policy knobs.  core.codec
# mirrors these (asserted at dispatch time there) and the tests' scalar
# reference encodes with core.codec's: a drift would silently break
# kernel-vs-reference byte identity.
HASH_LOG = 13
HASH_SIZE = 1 << HASH_LOG
MIN_MATCH = 4
MFLIMIT = 12          # a match must not start within the last 12 bytes
LAST_LITERALS = 5     # the last 5 bytes of a stream are always literals
RUN_STRIDE = 4        # interior byte-run positions keep a candidate only
                      # every RUN_STRIDE bytes (re-anchor bound)

_EMPTY = (np.empty(0, np.int64),) * 3


def match_events_slab(slab, starts, ends,
                      force: str | None = None
                      ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Greedy LZ4 match events for every stream of a concatenated slab.

    ``slab`` is a flat uint8 buffer (e.g. the ravelled output of
    ``pack_planes_slab``); ``starts``/``ends`` bound each stream's
    half-open byte range, disjoint and ascending (gaps — bypassed
    streams — are allowed and never touched).  Returns ``(pos, dist, mlen)`` int64 arrays sorted by
    global position: the matches the tests' per-stream scalar reference
    scan would select, bit for bit.

    ``force``: ``"numpy"`` pins the vectorized-numpy fallback,
    ``"device"`` pins the pallas+jnp path (interpret mode off the
    TPU); default dispatches through ``kernels.dispatch.choose``.
    """
    starts = np.asarray(starts, dtype=np.int64).ravel()
    ends = np.asarray(ends, dtype=np.int64).ravel()
    if starts.size == 0:
        return _EMPTY
    path = dispatch.choose("lz4", force)
    if path != dispatch.NUMPY:
        return _match_events_device(
            slab, starts, ends, interpret=path == dispatch.INTERPRET)
    buf = np.asarray(slab, dtype=np.uint8).ravel()
    return _match_events_numpy(buf, starts, ends)


# ---------------------------------------------------------------------------
# vectorized-numpy path (CPU production encoder)
# ---------------------------------------------------------------------------

def _words_hashes(buf: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """4-byte LE words + hashes for positions 0..N-4.

    Built in place (one uint32 accumulator, no shift temporaries); the
    hash fits HASH_LOG ≤ 16 bits so it is returned as uint16, which is
    what lets the table sort use numpy's radix path downstream."""
    w = buf[3:].astype(np.uint32)
    np.left_shift(w, np.uint32(8), out=w)
    np.bitwise_or(w, buf[2:-1], out=w)
    np.left_shift(w, np.uint32(8), out=w)
    np.bitwise_or(w, buf[1:-2], out=w)
    np.left_shift(w, np.uint32(8), out=w)
    np.bitwise_or(w, buf[:-3], out=w)
    h = w * np.uint32(2654435761)
    np.right_shift(h, np.uint32(32 - HASH_LOG), out=h)
    return w, h.astype(np.uint16)


_SWEEP_CAP = 32        # capped-LCP sweep bound for offsets > 1; NOT an
                       # output cap — selected matches that hit it are
                       # galloped to the true LCP during selection
_GALLOP_EAGER = 128    # ≤ this many sweep-capped candidates → gallop
                       # them all up front so selection runs flag-free;
                       # above it (long periodic data) gallop lazily on
                       # selection only, keeping worst-case work bounded


def _match_events_numpy(buf: np.ndarray, starts: np.ndarray,
                        ends: np.ndarray):
    """The numpy match path — the CPU production encoder.

    The expensive part of the hash table is the sort; this path
    *run-collapses* it first: a position whose word equals its left
    neighbour's word sits inside a byte run, so its previous occurrence
    is trivially ``pos - 1`` (same word, one back) and never needs the
    table.  Interior run positions are also redundant as table SOURCES —
    any later lookup that would land on one resolves to the run's last
    word-position instead — so only run-last and non-run positions enter
    the sort.  The run side itself never materializes per-position
    state: maximal byte runs are intersected with streams into
    *segments*, and the kept stride candidates (plus their exact match
    lengths, read off the run end) are generated segment-wise by ragged
    arithmetic.  On zero-heavy bitplane slabs that collapses most of the
    slab out of every O(n)-per-position stage; the remaining passes
    (lookup filter, capped-sweep LCP, pointer-jump greedy rounds) are
    O(candidates) each.
    """
    N = int(buf.size)
    if N < MIN_MATCH:
        return _EMPTY
    w, h = _words_hashes(buf)
    S = int(starts.size)
    sizes = ends - starts
    # word-valid positions per stream, contiguous per stream in j-domain
    cnt = np.maximum(sizes - 3, 0)
    ccum = np.cumsum(cnt)
    W = int(ccum[-1])
    if W == 0:
        return _EMPTY
    cbase = ccum - cnt
    sid_dt = np.uint16 if S <= 0xFFFF else np.int64
    sid_w = np.repeat(np.arange(S, dtype=sid_dt), cnt)
    # j-domain index ``j`` maps to position ``j + adj[sid]`` — kept as a
    # per-stream adjustment so no W-sized position array is ever built
    adj = (starts - cbase).astype(np.int32)

    # --- byte-run segmentation ----------------------------------------
    # maximal equal-byte runs [a, b]; a run holds word-run positions
    # (word == left neighbour's word) at a+1..b-3, so only runs of
    # length ≥ 5 matter.  Intersecting those with the streams gives
    # segments [lo, hi] of run positions — everything the run side
    # needs (table exclusion, stride candidates, match lengths) is
    # derived per segment, never per position.
    bnd = np.flatnonzero(buf[1:] != buf[:-1])    # last index of each run
    if bnd.size:
        # interior long runs: gap ≥ 5 between consecutive boundaries;
        # the first and last runs are handled as explicit edge cases
        li = np.flatnonzero(np.diff(bnd) >= MIN_MATCH + 1)
        ra = bnd[li] + 1
        rb = bnd[li + 1]
        if int(bnd[0]) >= MIN_MATCH:                # run before first bnd
            ra = np.concatenate(([0], ra))
            rb = np.concatenate(([bnd[0]], rb))
        if N - 1 - int(bnd[-1]) >= MIN_MATCH + 1:   # run after last bnd
            ra = np.concatenate((ra, [bnd[-1] + 1]))
            rb = np.concatenate((rb, [N - 1]))
    elif N >= MIN_MATCH + 1:                        # whole buf one run
        ra = np.asarray([0], dtype=np.int64)
        rb = np.asarray([N - 1], dtype=np.int64)
    else:
        ra = rb = np.empty(0, dtype=np.int64)
    if ra.size:
        s0 = np.minimum(np.searchsorted(ends, ra + 1, side="right"), S - 1)
        s1 = np.minimum(np.searchsorted(ends, rb - 3, side="right"), S - 1)
        nspan = s1 - s0 + 1
        segc = np.cumsum(nspan)
        nseg0 = int(segc[-1])
        segrun = np.repeat(np.arange(ra.size, dtype=np.int64), nspan)
        segsid = (np.arange(nseg0, dtype=np.int64)
                  - np.repeat(segc - nspan - s0, nspan))
        lo = np.maximum(ra[segrun] + 1, starts[segsid] + 1)
        hi = np.minimum(rb[segrun] - 3, ends[segsid] - 4)
        keep = lo <= hi
        segrun, segsid = segrun[keep], segsid[keep]
        lo, hi = lo[keep], hi[keep]
    else:
        segrun = segsid = lo = hi = np.empty(0, dtype=np.int64)

    # --- hash-table sort over the run-collapsed subset -----------------
    # run-interior positions ([lo, hi-1] per segment) leave the table;
    # run-LAST positions (hi) stay as sources but never look up
    jlo = cbase[segsid] + (lo - starts[segsid])
    jhi = jlo + (hi - lo)
    # subset = complement of the excluded [jlo, jhi-1] ranges, built
    # directly as ragged keep-ranges (segments are disjoint ascending in
    # j, with ≥ 2 positions between consecutive excluded ranges)
    klo = np.concatenate(([0], jhi))
    khi = np.concatenate((jlo, [W]))
    klen = khi - klo
    kcum = np.cumsum(klen)
    subset = (np.arange(int(kcum[-1]), dtype=np.int32)
              + np.repeat((klo - (kcum - klen)).astype(np.int32), klen))
    # run-last flags in the SUBSET domain (every jhi survives the cut,
    # so its subset index is exact)
    irl_sub = np.zeros(subset.size, dtype=bool)
    irl_sub[np.searchsorted(subset, jhi.astype(np.int32))] = True
    ssub = sid_w[subset]
    psub = subset + adj[ssub]
    hsub = h[psub]
    if S <= 0xFFFF:
        # ONE stable uint16 radix pass on the WRAPPED key: numpy only
        # radix-sorts ≤ 16-bit ints, and a full lexicographic sort isn't
        # needed — the subset is already sid-ascending, so groups whose
        # keys alias mod 2^16 (sids differing by a multiple of
        # 2^(16-HASH_LOG)) land concatenated in j order, never
        # interleaved, and the `same` test below cuts the seam between
        # them.  Adjacency is exact on (key16, sid): with equal sids,
        # equal wrapped keys force equal hashes (hash < 2^HASH_LOG) — no
        # widened key is ever materialized
        key16 = ((ssub.astype(np.uint16) << np.uint16(HASH_LOG))
                 + hsub)
        order = np.argsort(key16, kind="stable")
        k16o = key16[order]
        so = ssub[order]
        same = (k16o[1:] == k16o[:-1]) & (so[1:] == so[:-1])
    else:  # pragma: no cover - >65535 streams per flush group
        skeys = (ssub.astype(np.int64) << np.int64(HASH_LOG)) | hsub
        order = np.argsort(skeys, kind="stable")
        ks = skeys[order]
        same = ks[1:] == ks[:-1]
    # lookups: later element of a same-key pair, unless it is a run
    # position (their prev is pos-1, handled without the table).
    # prev_sub stores SUBSET indices, so position resolution is a psub
    # gather, never a W-sized one
    cand_idx = np.flatnonzero(same & ~irl_sub[order[1:]])
    prev_sub = np.full(subset.size, -1, dtype=np.int32)
    prev_sub[order[cand_idx + 1]] = order[cand_idx]

    # --- general candidates: window + word + end-of-stream rules -------
    gsel = np.flatnonzero(prev_sub >= 0)     # ascending j → ascending pos
    pj = psub[gsel]
    cj = psub[prev_sub[gsel]]
    okg = (pj - cj <= 0xFFFF) & (w[pj] == w[cj])
    pj, cj = pj[okg], cj[okg]
    sid_g = ssub[gsel[okg]]
    # a collision-induced dist-1 pair of unequal words is gone already
    # (word equality); true dist-1 equal-word pairs are run positions and
    # never reach the lookup set, so no run-stride test is needed here
    okg = pj < ends[sid_g] - MFLIMIT         # local < size - MFLIMIT
    pj, cj, sid_g = pj[okg], cj[okg], sid_g[okg]

    # --- run candidates + exact match lengths, straight off segments ---
    # kept positions per segment: the first run position ``lo`` (always
    # special: either local < 2 or the first interior of its byte run),
    # plus every RUN_STRIDE-aligned local.  Match length is read off the
    # byte-run end — no LCP pass for offset-1 matches.
    if lo.size:
        ends_seg = ends[segsid]
        hi2 = np.minimum(hi, ends_seg - (MFLIMIT + 1))
        f0 = lo + ((starts[segsid] - lo) % RUN_STRIDE)
        has = lo <= hi2
        nstr = np.where(has & (f0 <= hi2),
                        (hi2 - f0) // RUN_STRIDE + 1, 0)
        extra = (has & (f0 != lo)).astype(np.int64)
        tc = nstr + extra
        tcum = np.cumsum(tc)
        segi = np.repeat(np.arange(tc.size, dtype=np.int64), tc)
        within = (np.arange(int(tcum[-1]), dtype=np.int64)
                  - np.repeat(tcum - tc, tc))
        ex_i = extra[segi]
        pos_r = np.where(ex_i > within, lo[segi],
                         f0[segi] + RUN_STRIDE * (within - ex_i))
        sid_r = segsid[segi]
        mlen_r = np.minimum(rb[segrun][segi] + 1 - pos_r,
                            ends_seg[segi] - LAST_LITERALS - pos_r)
    else:
        pos_r = sid_r = mlen_r = np.empty(0, dtype=np.int64)

    if pos_r.size == 0 and pj.size == 0:
        return _EMPTY

    # --- LCP for general candidates: capped word sweep -----------------
    cap_full = ends[sid_g] - LAST_LITERALS - pj
    cap_g = np.minimum(cap_full, _SWEEP_CAP)
    mlen_g = np.full(pj.size, MIN_MATCH, dtype=np.int64)
    alive = np.arange(pj.size)
    k = MIN_MATCH
    while alive.size:
        word_ok = cap_g[alive] >= k + 4
        alive = alive[word_ok]
        if alive.size == 0:
            break
        eqw = w[pj[alive] + k] == w[cj[alive] + k]
        fail = alive[~eqw]
        if fail.size:
            b0 = (buf[pj[fail] + k] == buf[cj[fail] + k]).astype(np.int64)
            b1 = b0 & (buf[pj[fail] + k + 1] == buf[cj[fail] + k + 1])
            b2 = b1 & (buf[pj[fail] + k + 2] == buf[cj[fail] + k + 2])
            mlen_g[fail] = k + b0 + b1 + b2
        alive = alive[eqw]
        k += 4
        mlen_g[alive] = k
    arr = np.flatnonzero(mlen_g < cap_g)
    for _ in range(3):      # ≤3-byte exact tail (word room ran out)
        if arr.size == 0:
            break
        eq = buf[pj[arr] + mlen_g[arr]] == buf[cj[arr] + mlen_g[arr]]
        arr = arr[eq]
        mlen_g[arr] += 1
        arr = arr[mlen_g[arr] < cap_g[arr]]
    # sweep-capped candidates carry their TRUE LCP lazily: flagged, and
    # galloped out only if the greedy walk actually selects them
    flag_g = (mlen_g == _SWEEP_CAP) & (cap_full > _SWEEP_CAP)

    # --- merge run + general candidates in position order --------------
    C = int(pos_r.size + pj.size)
    pos_c = np.empty(C, dtype=np.int64)
    dist_c = np.empty(C, dtype=np.int64)
    mlen_c = np.empty(C, dtype=np.int64)
    flag_c = np.zeros(C + 1, dtype=bool)
    cap_c = np.empty(C, dtype=np.int64)
    # merge ranks: binary-search only the SMALLER side into the larger
    # (positions are disjoint across the two sides), then read the other
    # side's slots off the boolean complement — one searchsorted, not two
    if pj.size <= pos_r.size:
        at_g = np.arange(pj.size) + np.searchsorted(pos_r, pj)
        other = np.ones(C, dtype=bool)
        other[at_g] = False
        at_r = np.flatnonzero(other)
    else:
        at_r = np.arange(pos_r.size) + np.searchsorted(pj, pos_r)
        other = np.ones(C, dtype=bool)
        other[at_r] = False
        at_g = np.flatnonzero(other)
    pos_c[at_r] = pos_r
    pos_c[at_g] = pj
    dist_c[at_r] = 1
    dist_c[at_g] = pj - cj
    mlen_c[at_r] = mlen_r
    mlen_c[at_g] = mlen_g
    flag_c[at_g] = flag_g
    cap_c[at_g] = cap_full    # only flagged (general) slots are read
    # streams are contiguous ascending byte ranges, so the pos-sorted
    # candidate array groups by stream — per-stream bounds via bincount
    scnt = (np.bincount(sid_r, minlength=S)
            + np.bincount(sid_g, minlength=S))
    b_hi = np.cumsum(scnt)
    b_lo = b_hi - scnt
    bhi_c = np.repeat(b_hi, scnt)     # owning stream's bound, per slot

    # next-candidate resolution as a dense rank map: cs[q] = #candidates
    # with pos < q ≡ searchsorted(pos_c, q, "left").  pos_c is strictly
    # increasing, so the map is a step function materialized by ONE
    # ragged repeat of the inter-candidate widths — cheaper than a
    # bincount+cumsum and far cheaper than per-query binary search, here
    # and in the gallop paths below
    widths = np.diff(np.concatenate(([-1], pos_c, [N])))
    cs = np.repeat(np.arange(C + 1, dtype=np.int64), widths)
    nxt_c = cs[pos_c + mlen_c]
    fl = np.flatnonzero(flag_c[:C])
    if 0 < fl.size <= _GALLOP_EAGER:
        # few sweep-capped candidates: gallop them ALL to the true LCP
        # up front so selection runs flag-free.  Extending a node that
        # is never selected is harmless — match length only matters on
        # the selected path — so eager == lazy semantically.
        bb = buf.tobytes()
        for node in fl:
            node = int(node)
            p = int(pos_c[node])
            c = p - int(dist_c[node])
            m = int(mlen_c[node])
            mx = int(cap_c[node])
            while (m + 32 <= mx
                   and bb[c + m : c + m + 32] == bb[p + m : p + m + 32]):
                m += 32
            while m < mx and bb[c + m] == bb[p + m]:
                m += 1
            mlen_c[node] = m
        nxt_c[fl] = cs[pos_c[fl] + mlen_c[fl]]
        flag_c[:] = False

    # --- greedy selection: pointer-jump rounds across all streams ------
    # every live stream holds a cursor into the pos-sorted candidate
    # array; one round selects the cursor's match everywhere at once and
    # jumps past it.  Rounds run max-matches-per-stream times with ~2
    # small array ops each — no per-candidate python.
    # next-pointer per candidate: first candidate at or after the match
    # end, dead-ended (sentinel C) at the owning stream's boundary — so a
    # selection round is ONE gather, not a searchsorted
    nxt_ext = np.append(np.where(nxt_c < bhi_c, nxt_c, C), C)
    cur = np.where(b_lo < b_hi, b_lo, C)
    rounds = []
    if not flag_c.any():
        # flag-free: tight loop, liveness checked every 8 rounds
        # (overshoot rows are all-sentinel and filter out)
        live = True
        while live:
            for _ in range(8):
                rounds.append(cur)
                cur = nxt_ext[cur]
            live = bool((cur < C).any())
    else:  # lazy fallback: many capped candidates (long periodic data)
        bb = None
        while (cur < C).any():
            if flag_c[cur].any():
                # selected a sweep-capped match: gallop to the true LCP
                # now (selected matches never overlap → total work is
                # bounded) and repoint its next-jump past the full match
                if bb is None:
                    bb = buf.tobytes()
                for ci in np.flatnonzero(flag_c[cur]):
                    node = int(cur[ci])
                    p = int(pos_c[node])
                    c = p - int(dist_c[node])
                    m = int(mlen_c[node])
                    mx = int(cap_c[node])
                    while (m + 32 <= mx
                           and bb[c + m : c + m + 32]
                           == bb[p + m : p + m + 32]):
                        m += 32
                    while m < mx and bb[c + m] == bb[p + m]:
                        m += 1
                    mlen_c[node] = m
                    flag_c[node] = False
                    nj = int(cs[p + m])
                    nxt_ext[node] = nj if nj < bhi_c[node] else C
            rounds.append(cur)
            cur = nxt_ext[cur]
    if not rounds:
        return _EMPTY
    # column-major flatten: ascending within each stream, streams in
    # ascending byte order → globally ascending positions, no final sort
    sel = np.stack(rounds).ravel(order="F")
    sel = sel[sel < C]
    return pos_c[sel], dist_c[sel], mlen_c[sel]


# ---------------------------------------------------------------------------
# device path: pallas prep kernel + jnp passes under one jit
# ---------------------------------------------------------------------------

_PREP_BLOCK = 256     # rows per grid step; 128-byte minor axis (lane dim)
_PREP_C = 128


_HASH_MUL = np.int32(2654435761 - (1 << 32))   # the LZ4 multiplier, as int32


def _prep_kernel(b0_ref, b1_ref, b2_ref, b3_ref, w_ref, h_ref, run_ref):
    """Elementwise prep over shifted slab views: 4-byte LE word, hash,
    and run-boundary flag per position.  Pure array ops — the R6 lint
    holds this body host-sync-free.

    All int32: the TPU compiler has no unsigned compare.  The word keeps
    its uint32 bit pattern (only equality is asked of it downstream), the
    wrapping multiply has the same low 32 bits, and a logical shift
    extracts the same top ``HASH_LOG`` bits as the uint32 hash."""
    b0 = b0_ref[...].astype(jnp.int32)
    b1 = b1_ref[...].astype(jnp.int32)
    b2 = b2_ref[...].astype(jnp.int32)
    b3 = b3_ref[...].astype(jnp.int32)
    w = b0 | (b1 << 8) | (b2 << 16) | (b3 << 24)
    w_ref[...] = w
    h_ref[...] = lax.shift_right_logical(w * _HASH_MUL, 32 - HASH_LOG)
    # compare a difference: a compare straight on the widened bytes
    # lowers to an 8-bit compare, which the TPU compiler also refuses
    run_ref[...] = jnp.where(b0 - b1 != 0, 1, 0)


def _prep_pallas(buf, interpret: bool):
    """(N,) uint8 device slab → (w, h, runb) int32 arrays of length N
    (tail entries are garbage the downstream masks never read)."""
    n = buf.shape[0]
    tile = _PREP_BLOCK * _PREP_C
    pad = (-n - 3) % tile + 3          # room for the +3 shifted views
    bp = jnp.pad(buf, (0, pad))
    rows = (n + pad - 3) // _PREP_C
    shifted = [bp[i : i + rows * _PREP_C].reshape(rows, _PREP_C)
               for i in range(4)]
    br = min(_PREP_BLOCK, rows)
    grid = (rows // br,)
    spec = pl.BlockSpec((br, _PREP_C), lambda i: (i, 0))
    w, h, runb = pl.pallas_call(
        _prep_kernel,
        grid=grid,
        in_specs=[spec] * 4,
        out_specs=[spec] * 3,
        out_shape=[jax.ShapeDtypeStruct((rows, _PREP_C), jnp.int32)] * 3,
        interpret=interpret,
        name="lz4_prep_pallas",
    )(*shifted)
    return (w.reshape(-1)[:n], h.reshape(-1)[:n], runb.reshape(-1)[:n])


def _match_events_device(slab, starts, ends, interpret: bool):
    """Pallas prep + jnp match pipeline in one device launch; only the
    compacted event tensor returns to the host.

    On the device every stream gets a row of its own, ``L`` bytes wide
    (the longest stream, rounded up to a power of two).  Candidates never
    cross a stream, so the hash table becomes a sort along each row, and
    the next-candidate and run-end tables become suffix minima along each
    row: all compile in seconds, where one sort over the whole slab takes
    the TPU compiler minutes.  The slab length, the stream count and the
    event bound are rounded up to powers of two as well, so a serve run
    compiles a handful of shapes rather than one per flush; padding
    streams are empty rows that no cursor enters."""
    N = int(np.prod(slab.shape))
    if N < MIN_MATCH:
        return _EMPTY
    S = starts.size
    sizes = ends - starts
    L = dispatch.bucket(max(int(sizes.max()), 8))
    st = np.zeros(dispatch.bucket(S), dtype=np.int32)
    sz = np.zeros(dispatch.bucket(S), dtype=np.int32)
    st[:S], sz[:S] = starts, sizes
    # per-stream event bound: matches never overlap and are ≥ MIN_MATCH
    row_start = np.concatenate(([0], np.cumsum(sz // MIN_MATCH + 1)))
    E = dispatch.bucket(int(row_start[-1]))
    padded = np.zeros(dispatch.bucket(N), dtype=np.uint8)
    padded[:N] = np.asarray(slab, dtype=np.uint8).ravel()
    pos, dist, mlen, count = _device_match(
        jnp.asarray(padded), jnp.asarray(st), jnp.asarray(sz),
        jnp.asarray(row_start[:-1], dtype=np.int32),
        L=L, E=E, interpret=interpret)
    count = np.asarray(count)
    keep = np.concatenate([
        np.arange(row_start[s], row_start[s] + count[s]) for s in range(S)
    ])
    pos = np.asarray(pos)[keep].astype(np.int64)
    row = pos // L
    pos = pos - row * L + starts[row]          # row layout → slab offsets
    return (pos, np.asarray(dist)[keep].astype(np.int64),
            np.asarray(mlen)[keep].astype(np.int64))


def _suffix_min(x, fill: int):
    """Minimum over ``x[:, j:]`` for every ``j``: log2(L) shifted mins."""
    k = 1
    while k < x.shape[1]:
        pad = jnp.full((x.shape[0], k), fill, x.dtype)
        x = jnp.minimum(x, jnp.concatenate([x[:, k:], pad], axis=1))
        k *= 2
    return x


@functools.partial(jax.jit, static_argnames=("L", "E", "interpret"))
def _device_match(slab, starts, sizes, row_start, *, L: int, E: int,
                  interpret: bool):
    # the named scopes label each pass's ops in a profiler trace
    S = starts.shape[0]
    o = lax.broadcasted_iota(jnp.int32, (S, L), 1)          # row offset
    with jax.named_scope("lz4_prep"):
        src = jnp.clip(starts[:, None] + o, 0, slab.shape[0] - 1)
        inside = o < sizes[:, None]
        buf = jnp.where(inside, slab[src], 0).astype(jnp.uint8).reshape(-1)
        N = S * L
        w, h, runb = _prep_pallas(buf, interpret)
    iota = jnp.arange(N, dtype=jnp.int32)
    sid = iota // L
    valid = o + MIN_MATCH <= sizes[:, None]
    start_ok = (valid & (o < sizes[:, None] - MFLIMIT)).reshape(-1)
    stride_ok = ((o >= 2) & (o % RUN_STRIDE != 0)).reshape(-1)
    with jax.named_scope("lz4_hash_sort"):
        # stable sort of each row's hashes: the previous same-hash
        # occurrence is the sorted neighbour — the stream's whole hash
        # table in one pass
        keys = jnp.where(valid, h.reshape(S, L), HASH_SIZE)
        sk, so = lax.sort((keys, o), dimension=1, is_stable=True, num_keys=1)
        same = (sk[:, 1:] == sk[:, :-1]) & (sk[:, 1:] < HASH_SIZE)
        rows = lax.broadcasted_iota(jnp.int32, (S, L - 1), 0)
        prev = jnp.full((S, L), -1, dtype=jnp.int32).at[rows, so[:, 1:]].set(
            jnp.where(same, rows * L + so[:, :-1], -1)).reshape(-1)
    dist = iota - prev
    bufm2 = jnp.concatenate([jnp.zeros(2, buf.dtype), buf[:-2]])
    bufm1 = jnp.concatenate([jnp.zeros(1, buf.dtype), buf[:-1]])
    ok = (start_ok & (prev >= 0) & (dist <= 0xFFFF)
          & (w == w[jnp.clip(prev, 0, N - 1)]))
    ok &= ~((dist == 1) & stride_ok & (bufm2 == bufm1))
    with jax.named_scope("lz4_suffix_scans"):
        # next candidate at or after each position, and the last byte of
        # the byte run through it (every run length is capped inside its
        # stream)
        ncand = _suffix_min(jnp.where(ok, iota, N).reshape(S, L),
                            N).reshape(-1)
        run_last = _suffix_min(
            jnp.where(runb > 0, iota, iota | (L - 1)).reshape(S, L),
            N).reshape(-1)

    sids = jnp.arange(S, dtype=jnp.int32)
    max_end = sids * L + sizes - LAST_LITERALS

    def cursor_of(p):
        c = ncand[jnp.clip(p, 0, N - 1)]
        live = (p < N) & (c < N) & (sid[jnp.clip(c, 0, N - 1)] == sids)
        return jnp.where(live, c, N), live

    cur0, live0 = cursor_of(sids * L)

    def lcp_round(p, d, live):
        cap = max_end - p
        c = p - d
        run = d == 1
        m_run = jnp.minimum(run_last[jnp.clip(p, 0, N - 1)] - p + 1, cap)
        m = jnp.full((S,), MIN_MATCH, dtype=jnp.int32)

        def gallop_cond(st):
            m_, adv = st
            return jnp.any(adv)

        def gallop_body(st):
            m_, _ = st
            gi = jnp.clip(p + m_, 0, N - 1)
            ci = jnp.clip(c + m_, 0, N - 1)
            adv = live & ~run & (m_ + 4 <= cap) & (w[gi] == w[ci])
            return m_ + 4 * adv, adv

        m, _ = lax.while_loop(gallop_cond, gallop_body,
                              (m, jnp.ones((S,), bool)))
        for _ in range(3):      # exact ≤3-byte tail
            gi = jnp.clip(p + m, 0, N - 1)
            ci = jnp.clip(c + m, 0, N - 1)
            adv = live & ~run & (m < cap) & (buf[gi] == buf[ci])
            m = m + adv
        return jnp.where(run, m_run, jnp.where(live, m, MIN_MATCH))

    def cond(state):
        return jnp.any(state[3])

    def body(state):
        cur, count, out, live = state
        ci = jnp.clip(cur, 0, N - 1)
        p = iota[ci]
        d = dist[ci]
        m = lcp_round(p, d, live)
        slot = jnp.where(live, row_start + count, E)
        out = (out[0].at[slot].set(jnp.where(live, p, 0), mode="drop"),
               out[1].at[slot].set(jnp.where(live, d, 0), mode="drop"),
               out[2].at[slot].set(jnp.where(live, m, 0), mode="drop"))
        count = count + live
        # a cursor that jumped into another stream's row is dead
        ncur, nlive = cursor_of(jnp.where(live, p + m, N))
        return (ncur, count, out, nlive & live)

    out0 = tuple(jnp.zeros(E + 1, jnp.int32) for _ in range(3))
    with jax.named_scope("lz4_cursor_loop"):
        _, count, out, _ = lax.while_loop(
            cond, body, (cur0, jnp.zeros(S, jnp.int32), out0, live0))
    return out[0][:E], out[1][:E], out[2][:E], count
