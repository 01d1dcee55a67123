"""Request-batched CXL Type-3 tier store — Plain / GComp / TRACE (Table III).

The paper's central claim is that the *device-internal representation*
(word-major vs channel-major bit-plane) is swappable behind an unmodified
CXL.mem interface.  This module makes that boundary explicit:

* hosts speak **typed requests** — :class:`WriteReq` / :class:`ReadReq`
  descriptors that name a key, a payload kind (``tensor`` or ``kv``
  stream), a precision view and an optional block range;
* the device answers with **per-request receipts** — :class:`Receipt`
  carries the DRAM / link / index traffic and a first-order latency
  estimate for exactly that request, so traffic attribution is per-page /
  per-layer instead of one global counter blob (``DeviceStats`` remains as
  the running aggregate of all receipts);
* the internal representation is a **layout strategy** —
  :class:`WordLayout` (raw words), :class:`WordLayout` + codec (GComp's
  inline 4 KB block compression) or :class:`BitplaneLayout` (TRACE's
  bit-plane substrate, optionally with the cross-token KV transform of
  Fig. 8) — composed with the codec registry.  ``PlainDevice`` /
  ``GCompDevice`` / ``TraceDevice`` are thin :class:`TierStore`
  configurations kept for compatibility.

Batched submission is also a performance feature, in BOTH directions.  A
read batch's blocks are grouped by fetched plane-set and decoded in
vectorized numpy passes — one plane-unpack and one reconstruction call
per group, not per 4 KB block (see ``BitplaneLayout.decode_batch``).  A
write batch (sync or async posting group) stages every pending block into
one encode slab and encodes it in a few vectorized passes — one batched
KV transform, one plane pack (pallas kernel on accelerator backends) and
ONE ``codec.compress_batch`` over every (plane, block) stream — instead
of O(blocks x planes) python-level calls (see ``Layout.encode_batch`` /
``TierStore._post_writes``).  The per-block pipeline survives as
``Layout.encode_batch_scalar`` (``TierStore(batched_encode=False)``),
byte-identical by the encode differential tests.

Accounting conventions (per read):
  * ``dram_bytes``  — bytes the device DRAM actually serves (compressed
    planes for TRACE, compressed 4 KB blocks for GComp, raw words for
    Plain).  Plane-aligned fetch physically skips unfetched planes.
  * ``link_bytes``  — host-visible payload returned over CXL.mem (the
    reconstructed view; controller-side decompression per Fig. 11).
  * ``index_bytes`` — metadata traffic (64 B/entry on an index-cache miss).

Legacy shims (``write_tensor`` / ``read_tensor`` / ``write_kv`` /
``read_kv`` / ``flush_kv``) forward to :meth:`TierStore.submit` and are
kept so existing call sites keep working; new code should submit request
batches directly.

Namespaces: multi-stream consumers prefix their keys with a per-stream /
per-request namespace (``s0.``, ``r17.``).  :meth:`TierStore.delete_prefix`
retires a whole namespace in one call — blocks, staged KV windows and
index-cache entries — returning its stored capacity to ``stats``; this is
how the continuous-batching scheduler frees a finished request's pages
for queued admissions.

Asynchronous submission (the queued front-end):

``submit_async(requests) -> list[Ticket]`` enqueues a batch without
executing the reads.  Writes are *posted* — they commit immediately, in
listed order, exactly as :meth:`TierStore.submit` would — while reads
enter a bounded in-flight window (``window`` requests).  The scheduler
executes queued reads as coalesced groups: when the window fills, when a
:meth:`Ticket.wait` / :meth:`TierStore.drain` forces completion, or when
a hazard demands it.  A group decodes through the same vectorized
batched-read path as a sync batch, so reads from *different*
``submit_async`` calls coalesce into one slab decode.

Ordering semantics (these make async execution byte-identical to sync):

* within one ``submit_async`` call, writes post before reads enqueue —
  the same writes-drain-first rule as a single ``submit`` batch;
* across calls, program order is preserved per key: posting a write to a
  key with queued reads first flushes the queue (write-after-read
  fence), and ``submit`` / ``delete`` flush the queue before touching
  device state, so a late sync caller never observes stale ordering;
* queued reads execute in submission order (groups are queue prefixes),
  so index-cache hit/miss accounting is identical to the sync path.

Receipts from queued reads additionally carry ``queue_delay_s`` (time
spent behind earlier requests of the same flush group on the shared
DDR + link pipes) and an overlap-adjusted ``latency_s`` from
:class:`LinkModel.schedule` — the fixed request overhead is paid once
per group and transfers pipeline, which is what makes a drained batch
faster than the sum of serialized sync requests (the paper's decode /
fetch overlap at 128k context).  ``service_s`` keeps the serialized
service time for comparison.

Latency pricing carries ACROSS groups through a device-global busy
clock: posted writes and window-overflow flushes advance per-pipe busy
frontiers without advancing host time, so later groups queue behind
their residual occupancy (write-heavy many-stream receipts price
cross-boundary contention); a wait (sync read, ``drain``,
``Ticket.wait``) advances host time to delivery, and
:meth:`TierStore.quiesce` idles the host until the pipes drain.  The
clock only shapes ``queue_delay_s``/``latency_s`` — byte accounting, and
therefore the receipts-sum == ``DeviceStats`` invariant, is untouched.

Residency ledger (the physical-capacity control signal):

The store keeps a live per-key ledger of *stored* bytes — compressed
payload planes plus the 64 B/block index entry — updated at every block
commit (``_encode_commit`` → :meth:`TierStore._commit`), decremented by
:meth:`TierStore.delete` / :meth:`TierStore.delete_prefix` and by
in-place plane truncation.  :meth:`TierStore.resident_bytes` sums any
key-prefix namespace (a request's ``r{id}.`` keys, or the whole device
with an empty prefix) and :meth:`TierStore.compression_ratio` reports
the namespace's logical/physical ratio.  The invariant — the ledger
equals the sum of stored payload+index bytes at all times, under any
interleaving of writes, deletes and truncations — is property-tested.
This is what lets admission control reason about the *physical* KV
footprint instead of the logical projection (a trace device stores KV
at >2x compression, so it can admit a correspondingly larger batch).

Precision-elastic reclamation: plane-aligned layouts additionally
support :meth:`TierStore.truncate_planes` — dropping the low-order
mantissa planes of already-stored blocks *in place* (paper §III-C: the
bit-plane substrate makes precision a storage knob, not just a fetch
knob).  Truncation reclaims the dropped planes' payload bytes (returned
to the caller and reconciled against the ledger), records the surviving
:class:`PrecisionView` on each block, and later reads are served at the
intersection of the requested and stored views — bit-identical to
``reconstruct_u16`` applied at that view.  Word layouts store opaque
compressed containers and report truncation unsupported.
"""

from __future__ import annotations

import dataclasses
import os
from typing import (
    Collection, Dict, List, Optional, Sequence, Set, Tuple, Union,
)

import numpy as np

from .. import obs
from . import codec as codecs
from .bitplane import (
    BF16_BITS,
    BLOCK_ELEMS,
    iter_blocks,
    pack_planes,
    unpack_planes_subset,
)
from .kv_transform import (
    KVBlockMeta, kv_forward, kv_forward_batch, kv_inverse_batch,
)
from .precision import EXP_BITS, PrecisionView, FULL, SCORE, reconstruct_u16

INDEX_ENTRY_BYTES = 64  # paper §III-D: one compact entry per 4 KB block

# Request payload kinds.
TENSOR = "tensor"
KV = "kv"


# ---------------------------------------------------------------------------
# Typed requests + receipts
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class WriteReq:
    """Host→device write descriptor.

    ``kind=TENSOR``: ``data`` is any-shape uint16; stored block-by-block.
    ``kind=KV``: ``data`` is token-major ``(t, C)`` uint16 rows appended to
    the stream ``key``; full windows are committed as they fill and
    ``flush=True`` commits any partial window at the end of the request.
    """

    key: str
    data: np.ndarray
    kind: str = TENSOR
    flush: bool = True
    tag: str = ""


@dataclasses.dataclass(frozen=True)
class ReadReq:
    """Device→host read descriptor.

    ``view`` selects the precision alias (plane-aligned fetch on bit-plane
    layouts; word layouts always move full containers and reconstruct
    host-side).  ``block_range=(lo, hi)`` restricts the read to that slice
    of the key's block list; ranged tensor reads return flat uint16.
    """

    key: str
    kind: str = TENSOR
    view: PrecisionView = FULL
    block_range: Optional[Tuple[int, int]] = None
    tag: str = ""


@dataclasses.dataclass(frozen=True)
class GatherReq:
    """Device-side top-k gather descriptor (the PNM read mode).

    The host names the candidate ``keys`` (spilled KV pages resident on
    the device), a flat ``(channels,)`` float32 query ``digest`` and a
    winner count ``k``.  The device scores every candidate ON the device
    — a plane-subset decode at ``score_view`` (sign + the compressible
    exponent planes ONLY by default, so scoring DRAM traffic is a small
    fraction of a full fetch) feeding the ``kernels.pnm_score`` kernel —
    and returns full-precision data for only the top-k pages, so link
    bytes drop from O(candidates) to O(k · page) + 4 B/candidate of
    shipped scores.  ``views`` optionally pins a per-key winner fetch
    view (position-aligned with ``keys``; ``FULL`` when omitted), which
    is what makes a gather at ``k >= len(keys)`` byte-identical to
    individual :class:`ReadReq` submissions at the same views.

    Ties on equal scores break by candidate list position (stable,
    host-chosen order), so winner selection is deterministic across
    sync/async submission and shard counts.
    """

    keys: Tuple[str, ...]
    digest: np.ndarray
    k: int
    kind: str = KV
    views: Optional[Tuple[PrecisionView, ...]] = None
    score_view: PrecisionView = SCORE
    tag: str = ""

    @property
    def key(self) -> str:
        """First candidate key — routing/repr convenience so a gather
        slots into code paths that label requests by ``request.key``."""
        return self.keys[0] if self.keys else ""


Request = Union[WriteReq, ReadReq, GatherReq]


def _req_keys(req: Request) -> frozenset:
    """Every device key one request touches (hazard-fence granularity)."""
    if isinstance(req, GatherReq):
        return frozenset(req.keys)
    return frozenset((req.key,))


@dataclasses.dataclass
class GatherResult:
    """Winner set of one executed :class:`GatherReq`.

    ``scores`` covers EVERY candidate (in the request's ``keys`` order,
    float32) — the host ledger can fold the full ranking into page
    importances, not just the winners.  ``keys`` / ``indices`` /
    ``data`` are the winners in descending-score order (ties by
    candidate position), ``data`` holding exactly the bytes a plain
    read of that key at its winner view would have returned.
    """

    keys: List[str]
    indices: List[int]
    scores: np.ndarray
    data: List[np.ndarray]


@dataclasses.dataclass
class Receipt:
    """Per-request traffic + latency accounting (and data, for reads).

    Field names mirror :class:`DeviceStats`; summing any field across the
    receipts of a session reproduces the corresponding aggregate delta
    exactly — this is tested.
    """

    key: str
    op: str                       # "write" | "read"
    kind: str = TENSOR
    tag: str = ""
    blocks: int = 0
    dram_bytes_read: int = 0
    dram_bytes_written: int = 0
    dram_bytes_stored: int = 0    # capacity delta (writes)
    raw_bytes_stored: int = 0     # logical (uncompressed) delta (writes)
    link_bytes_in: int = 0
    link_bytes_out: int = 0
    index_bytes: int = 0
    index_hits: int = 0
    index_misses: int = 0
    codec_blocks: int = 0         # payload streams that hit the bypass rule
    codec_bypass: int = 0         # ... of which were stored raw (§III-D)
    latency_s: float = 0.0        # delivery time: queue_delay_s + service
    queue_delay_s: float = 0.0    # wait behind earlier in-flight requests
    service_s: float = 0.0        # serialized service time (sync latency)
    device_compute_s: float = 0.0  # device-side PNM scoring time (gathers)
    device_id: int = 0            # which device in a fleet served this
    data: Optional[np.ndarray] = None
    gather: Optional[GatherResult] = None   # winner set (gather ops only)

    @property
    def dram_bytes(self) -> int:
        return self.dram_bytes_read + self.dram_bytes_written

    @property
    def link_bytes(self) -> int:
        return self.link_bytes_in + self.link_bytes_out


@dataclasses.dataclass(frozen=True)
class LinkModel:
    """First-order service-time model for a receipt (paper §IV-B numbers).

    ``base_s`` is the fixed per-request overhead.  The named device
    configurations derive it from the calibrated controller pipeline via
    :meth:`for_design` (Table V load-to-use: Plain 71 / GComp 84 / TRACE
    89 cycles @ 2 GHz), so receipt latency reflects the per-design
    front-end + metadata + scheduling + DRAM-window cost; passing an
    explicit ``base_s`` (or a whole ``link_model``) overrides the anchor
    with a constant — which is what latency-shape tests do.
    """

    ddr_bw: float = 256e9         # device-side DDR
    link_bw: float = 512e9        # CXL.mem per direction
    base_s: float = 1e-6          # fixed request overhead
    pnm_ops_s: float = 2e12       # near-memory scoring throughput (elem/s)

    @classmethod
    def for_design(cls, design: str, comp_ratio: float = 1.5,
                   **kw) -> "LinkModel":
        """A link model whose fixed overhead is the calibrated
        load-to-use pipeline latency of ``design`` (controller.py
        anchors, Fig. 22/23) at the given compression ratio."""
        from .controller import load_to_use_ns

        return cls(base_s=load_to_use_ns(design, comp_ratio=comp_ratio)
                   * 1e-9, **kw)

    def latency(self, dram_bytes: int, link_bytes: int) -> float:
        return self.base_s + max(dram_bytes / self.ddr_bw,
                                 link_bytes / self.link_bw)

    def device_compute(self, elems: int) -> float:
        """Time the device's near-memory unit spends scoring ``elems``
        candidate elements for one gather (a third resource next to the
        DDR and link pipes; it extends delivery, never byte traffic)."""
        return elems / self.pnm_ops_s

    def schedule(
        self, traffic: Sequence[Tuple[int, int]],
        ddr_backlog_s: float = 0.0, link_backlog_s: float = 0.0,
    ) -> List[Tuple[float, float]]:
        """Completion model for one in-flight group sharing DDR + link.

        ``traffic`` is ordered ``(dram_bytes, link_bytes)`` per request.
        Request *i* is delivered once both pipes have moved its cumulative
        bytes; the fixed request overhead is paid once per group.  Returns
        ``(queue_delay_s, latency_s)`` per request, where ``latency_s`` is
        the delivery time measured from group issue and ``queue_delay_s``
        is that minus the request's own serialized service time — i.e. the
        wait behind earlier requests on the occupied pipes.

        ``ddr_backlog_s`` / ``link_backlog_s`` carry residual pipe
        occupancy from EARLIER groups the host did not wait for (posted
        writes, window-overflow flushes): this group's requests queue
        behind that backlog, which is how many-stream receipts price
        cross-group contention (the device-global busy clock kept by
        :class:`TierStore`).
        """
        out: List[Tuple[float, float]] = []
        cum_dram = cum_link = 0
        for dram, link in traffic:
            service = self.latency(dram, link)
            cum_dram += dram
            cum_link += link
            done = self.base_s + max(ddr_backlog_s + cum_dram / self.ddr_bw,
                                     link_backlog_s + cum_link / self.link_bw)
            out.append((max(done - service, 0.0), done))
        return out


@dataclasses.dataclass
class DeviceStats:
    """Running aggregate of every receipt the store has issued."""

    dram_bytes_stored: int = 0      # capacity footprint (compressed)
    dram_bytes_read: int = 0
    dram_bytes_written: int = 0
    link_bytes_out: int = 0
    link_bytes_in: int = 0
    index_bytes: int = 0
    index_hits: int = 0
    index_misses: int = 0
    blocks: int = 0
    raw_bytes_stored: int = 0       # logical (uncompressed) footprint
    codec_blocks: int = 0           # payload streams offered to the codec
    codec_bypass: int = 0           # ... stored raw (bypass, paper §III-D)
    device_compute_s: float = 0.0   # near-memory scoring time (PNM gathers)

    @property
    def bypass_rate(self) -> float:
        """Fraction of codec payload streams stored raw (bypass rate)."""
        return self.codec_bypass / max(self.codec_blocks, 1)

    def reset_traffic(self):
        self.dram_bytes_read = 0
        self.dram_bytes_written = 0
        self.link_bytes_out = 0
        self.link_bytes_in = 0
        self.index_bytes = 0
        self.index_hits = self.index_misses = 0
        self.device_compute_s = 0.0

    def apply(self, r: Receipt):
        self.dram_bytes_read += r.dram_bytes_read
        self.dram_bytes_written += r.dram_bytes_written
        self.dram_bytes_stored += r.dram_bytes_stored
        self.raw_bytes_stored += r.raw_bytes_stored
        self.link_bytes_in += r.link_bytes_in
        self.link_bytes_out += r.link_bytes_out
        self.index_bytes += r.index_bytes
        self.index_hits += r.index_hits
        self.index_misses += r.index_misses
        self.blocks += r.blocks
        self.codec_blocks += r.codec_blocks
        self.codec_bypass += r.codec_bypass
        self.device_compute_s += r.device_compute_s

    @property
    def compression_ratio(self) -> float:
        return self.raw_bytes_stored / max(self.dram_bytes_stored, 1)


def _ns_match(key: str, prefix: str) -> bool:
    """Namespace-delimited prefix match for ledger/delete queries.

    Key namespaces are ``.``-delimited (``r1.L0.k.0``, ``shared.<hash>.…``),
    so a query for ``"r1"`` must never claim ``r10.``'s keys: an empty
    prefix matches everything, an exact key matches itself, and otherwise
    the prefix is extended to the next ``.`` boundary before matching.
    """
    if not prefix:
        return True
    if key == prefix:
        return True
    if not prefix.endswith("."):
        prefix += "."
    return key.startswith(prefix)


# ---------------------------------------------------------------------------
# Runtime invariant sanitizer (TRACE_SANITIZE=1 / TierStore(sanitize=True))
# ---------------------------------------------------------------------------

class SanitizerViolation(AssertionError):
    """A live accounting invariant broke under sanitize mode.

    Carries the violated invariant's name, the key (or key prefix) it
    was detected on, and the expected/actual values — the runtime
    counterpart of the ``tools/tracecheck`` static rules.
    """

    def __init__(self, invariant: str, key: str = "", expected=None,
                 actual=None, detail: str = ""):
        self.invariant = invariant
        self.key = key
        self.expected = expected
        self.actual = actual
        self.detail = detail
        msg = (f"[{invariant}] key={key!r} expected={expected!r} "
               f"actual={actual!r}")
        if detail:
            msg += f" — {detail}"
        super().__init__(msg)


class _MirroredStats(DeviceStats):
    """DeviceStats wired to the sanitizer's shadow aggregate so a
    caller's ``stats.reset_traffic()`` resets both sides in lockstep
    (direct field pokes still desync and trip the sanitizer — that is
    the point)."""

    def __init__(self, mirror: DeviceStats):
        super().__init__()
        self._mirror = mirror

    def reset_traffic(self):
        super().reset_traffic()
        self._mirror.reset_traffic()


class _Sanitizer:
    """Always-on invariant checks for one :class:`TierStore`.

    Enabled by ``TierStore(sanitize=True)`` or ``TRACE_SANITIZE=1``;
    zero overhead when off (the store holds ``None``).  Validated at
    every commit boundary (write post, read group, KV flush) and on the
    retirement paths (``delete`` / ``delete_prefix`` /
    ``truncate_planes``):

    * ``ledger-stored-equality`` — each residency-ledger row equals its
      key's stored payload/index/raw bytes and block count, and the
      ledger totals equal the stats capacity fields;
    * ``receipt-conservation`` — ``stats`` equals a shadow aggregate
      rebuilt from every receipt through the sanctioned helpers
      (receipts-sum == ``DeviceStats``);
    * ``busy-clock-monotonic`` — host time and the per-pipe busy
      frontiers never move backwards;
    * ``inflight-window-bound`` — queued reads never exceed ``window``;
    * ``retire-cleanup`` — a delete leaves no orphaned blocks, ledger
      rows, staging buffers, shapes, channel metadata or index-cache
      entries behind;
    * ``refcount-conservation`` — each ledger row's reference count
      equals a shadow count rebuilt from every commit / ``acquire`` /
      ``release`` (shared pages are freed exactly when the last
      reference retires, never earlier or later).
    """

    __slots__ = ("store", "shadow", "refs", "_now", "_ddr", "_link")

    _LEDGER_FIELDS = ("payload_bytes", "index_bytes", "raw_bytes", "blocks")
    _CAPACITY_FIELDS = ("dram_bytes_stored", "raw_bytes_stored", "blocks")

    def __init__(self, store: "TierStore"):
        self.store = store
        self.shadow = DeviceStats()
        self.refs: Dict[str, int] = {}
        self._now = self._ddr = self._link = 0.0

    def boundary(self, touched: Optional[Set[str]] = None):
        """Full commit-boundary validation (per-key checks limited to
        ``touched`` keys; aggregates always checked)."""
        self.check_clock()
        self.check_window()
        self.check_ledger(touched)
        self.check_conservation()

    def check_clock(self):
        s = self.store
        for attr, last in (("_now_s", self._now), ("_ddr_free_s", self._ddr),
                           ("_link_free_s", self._link)):
            cur = getattr(s, attr)
            if cur < last - 1e-12:
                raise SanitizerViolation(
                    "busy-clock-monotonic", key=attr,
                    expected=f">= {last!r}", actual=cur,
                    detail="busy-clock frontier moved backwards",
                )
        self._now, self._ddr, self._link = (s._now_s, s._ddr_free_s,
                                            s._link_free_s)

    def check_window(self):
        s = self.store
        if len(s._queue) > s.window:
            raise SanitizerViolation(
                "inflight-window-bound", expected=f"<= {s.window}",
                actual=len(s._queue),
                detail="queued reads exceed the in-flight window",
            )

    def check_ledger(self, touched: Optional[Set[str]] = None):
        s = self.store
        if set(s._ledger) != set(s._tensors):
            only_l = sorted(set(s._ledger) - set(s._tensors))
            only_t = sorted(set(s._tensors) - set(s._ledger))
            raise SanitizerViolation(
                "ledger-stored-equality", key=(only_l + only_t)[0],
                expected="ledger keys == stored keys",
                actual=f"ledger-only={only_l[:3]} stored-only={only_t[:3]}",
            )
        keys = (s._ledger if touched is None
                else [k for k in touched if k in s._ledger])
        for key in keys:
            entry = s._ledger[key]
            blocks = s._tensors[key]
            want = (sum(b.stored_bytes for b in blocks),
                    len(blocks) * INDEX_ENTRY_BYTES,
                    sum(b.valid_elems for b in blocks) * 2, len(blocks))
            got = tuple(getattr(entry, f) for f in self._LEDGER_FIELDS)
            if want != got:
                raise SanitizerViolation(
                    "ledger-stored-equality", key=key,
                    expected=dict(zip(self._LEDGER_FIELDS, want)),
                    actual=dict(zip(self._LEDGER_FIELDS, got)),
                    detail="residency ledger row != stored bytes",
                )
            want_refs = self.refs.get(key, 1)
            if entry.refs != want_refs or entry.refs < 1:
                raise SanitizerViolation(
                    "refcount-conservation", key=key,
                    expected=want_refs, actual=entry.refs,
                    detail="ledger refcount drifted from the "
                           "acquire/release shadow",
                )
        totals = (sum(e.payload_bytes for e in s._ledger.values()),
                  sum(e.raw_bytes for e in s._ledger.values()),
                  sum(e.blocks for e in s._ledger.values()))
        stat = tuple(getattr(s.stats, f) for f in self._CAPACITY_FIELDS)
        if totals != stat:
            raise SanitizerViolation(
                "ledger-stored-equality",
                expected=dict(zip(self._CAPACITY_FIELDS, totals)),
                actual=dict(zip(self._CAPACITY_FIELDS, stat)),
                detail="ledger totals != stats capacity fields",
            )

    def check_conservation(self):
        for f in dataclasses.fields(DeviceStats):
            want = getattr(self.shadow, f.name)
            got = getattr(self.store.stats, f.name)
            if want != got:
                raise SanitizerViolation(
                    "receipt-conservation", key=f.name, expected=want,
                    actual=got,
                    detail="stats field drifted from the receipts-sum "
                           "shadow (mutated outside the sanctioned "
                           "helpers?)",
                )

    def check_retired(self, prefix: Optional[str] = None,
                      key: Optional[str] = None,
                      survivors: Collection[str] = ()):
        """``survivors``: keys a namespace delete legitimately left behind
        because other references still hold them (refcount > 0)."""
        s = self.store

        def gone(k: str) -> bool:
            if k in survivors:
                return False
            return k == key if key is not None else _ns_match(k, prefix)

        stores = (("stored blocks", s._tensors), ("ledger", s._ledger),
                  ("shapes", s._shapes), ("kv staging", s._kv_staging),
                  ("kv channels", s._kv_channels))
        target = key if key is not None else prefix
        for what, d in stores:
            left = sorted(k for k in d if gone(k))
            if left:
                raise SanitizerViolation(
                    "retire-cleanup", key=target,
                    expected="no surviving entries",
                    actual=f"{what}: {left[:3]}",
                    detail="delete left orphaned keys behind",
                )
        left = sorted({k[0] for k in s._index._lru if gone(k[0])})
        if left:
            raise SanitizerViolation(
                "retire-cleanup", key=target,
                expected="no surviving entries",
                actual=f"index cache: {left[:3]}",
                detail="delete left orphaned index-cache entries behind",
            )


@dataclasses.dataclass
class _Block:
    """One 4 KB logical block in device DRAM."""

    payloads: List[bytes]            # per-plane (bit-plane) or single (word)
    flags: List[int]                 # codec.RAW / codec.COMPRESSED
    valid_elems: int                 # host-visible elements
    padded_elems: int                # elements the payloads encode (≥ valid)
    kv_meta: Optional[KVBlockMeta] = None
    view: Optional[PrecisionView] = None   # surviving view after truncation

    @property
    def stored_bytes(self) -> int:
        return sum(len(p) for p in self.payloads)


@dataclasses.dataclass
class ResidencyEntry:
    """One key's row in the physical-footprint residency ledger.

    ``refs`` counts outstanding references to the key.  Private pages
    stay at 1 for their whole life; content-addressed ``shared.`` pages
    gain a reference per :meth:`TierStore.acquire` and lose one per
    :meth:`TierStore.release` — the stored bytes are counted once here
    regardless of how many referers hold the page, and are freed exactly
    when the count reaches zero.
    """

    payload_bytes: int = 0      # stored (post-compression) plane payloads
    index_bytes: int = 0        # 64 B per committed block (metadata)
    raw_bytes: int = 0          # logical (uncompressed) footprint
    blocks: int = 0
    refs: int = 1               # outstanding references (shared pages > 1)

    @property
    def physical_bytes(self) -> int:
        return self.payload_bytes + self.index_bytes


class _EncodeSlab:
    """Per-posting-group staging area for deferred batched encoding.

    Write staging appends each pending block here (with its key, receipt
    and optional KV meta, kept in parallel lists); the group is then
    packed+compressed in one ``Layout.encode_batch`` pass and committed in
    staging order — the write-side mirror of the read side's shared decode
    slab.  KV windows are staged UNtransformed (``kv_windows`` slot): the
    exponent-delta transform is independent per window, so it too defers
    and runs as a batched pass at encode time (``kv_forward_batch``).
    """

    __slots__ = ("keys", "recs", "chunks", "valids", "metas", "kv_windows")

    def __init__(self):
        self.keys: List[str] = []
        self.recs: List[Receipt] = []
        self.chunks: List[Optional[np.ndarray]] = []
        self.valids: List[int] = []
        self.metas: List[Optional[KVBlockMeta]] = []
        self.kv_windows: List[Optional[np.ndarray]] = []

    def add(self, key: str, rec: Receipt, chunk: Optional[np.ndarray],
            valid: int, meta: Optional[KVBlockMeta] = None,
            kv_window: Optional[np.ndarray] = None):
        self.keys.append(key)
        self.recs.append(rec)
        self.chunks.append(chunk)
        self.valids.append(valid)
        self.metas.append(meta)
        self.kv_windows.append(kv_window)

    def clear(self):
        self.keys.clear()
        self.recs.clear()
        self.chunks.clear()
        self.valids.clear()
        self.metas.clear()
        self.kv_windows.clear()


class _IndexCache:
    """On-chip plane-index cache (paper Fig. 11, metadata management)."""

    def __init__(self, capacity_entries: int = 4096):
        self.capacity = capacity_entries
        self._lru: Dict[tuple, None] = {}

    def access(self, key: tuple) -> bool:
        hit = key in self._lru
        if hit:
            self._lru.pop(key)
        self._lru[key] = None
        if len(self._lru) > self.capacity:
            self._lru.pop(next(iter(self._lru)))
        return hit

    def evict_stream(self, stream: str):
        """Drop every cached entry of one stream key (entries are
        ``(stream, block_index)`` tuples) — deleting a key must not leave
        dangling index entries that a later same-named key would "hit"."""
        for k in [k for k in self._lru if k[0] == stream]:
            self._lru.pop(k)

    def evict_prefix(self, prefix: str):
        """Drop every cached entry whose stream key is in ``prefix``'s
        namespace (one LRU pass for a whole-namespace delete)."""
        for k in [k for k in self._lru if _ns_match(k[0], prefix)]:
            self._lru.pop(k)


# ---------------------------------------------------------------------------
# Layout strategies — the device-internal representation
# ---------------------------------------------------------------------------

class Layout:
    """Encodes 4 KB blocks to payloads and decodes request batches back.

    ``plane_aligned`` declares whether a reduced :class:`PrecisionView`
    physically cuts DRAM traffic (TRACE Mechanism II); word layouts always
    move full containers and reconstruct host-side (paper Issue 2).
    ``kv_transform`` enables the cross-token exponent-delta transform on KV
    windows (TRACE Mechanism I).  ``uses_codec`` marks layouts whose
    payloads go through the inline codec (drives bypass-rate accounting).

    Encoding is batched two ways: :meth:`encode_batch` is the production
    path — a whole flush group in a few vectorized passes (one plane pack,
    one ``compress_batch`` over every payload stream) — while
    :meth:`encode_batch_scalar` is the O(blocks x planes) per-block
    reference the device originally shipped with.  Both must produce
    byte-identical payloads and flags; the encode differential tests hold
    them to that.
    """

    name = "layout"
    plane_aligned = False
    kv_transform = False
    uses_codec = False

    def encode_batch(self, chunks: Sequence[np.ndarray],
                     codec: str) -> List[Tuple[List[bytes], List[int]]]:
        """Vectorized batch encode: one entry ``(payloads, flags)`` per chunk."""
        raise NotImplementedError

    def encode_batch_scalar(self, chunks: Sequence[np.ndarray],
                            codec: str) -> List[Tuple[List[bytes], List[int]]]:
        """Per-block reference encode (parity oracle + benchmark baseline)."""
        raise NotImplementedError

    def fetched_payloads(self, block: _Block, view: PrecisionView) -> Sequence[int]:
        """Payload indices a read with ``view`` physically touches."""
        raise NotImplementedError

    def decode_batch(self, blocks: Sequence[_Block], view: PrecisionView,
                     codec: str) -> List[np.ndarray]:
        """Per-block host-visible uint16 (valid-trimmed, reconstructed)."""
        raise NotImplementedError


class WordLayout(Layout):
    """Word-major containers; optional generic inline block compression."""

    plane_aligned = False
    kv_transform = False

    def __init__(self, compress: bool):
        self.compress = compress
        self.uses_codec = compress
        self.name = "word-comp" if compress else "word"

    def encode_batch(self, chunks, codec):
        raws = [chunk.tobytes() for chunk in chunks]
        if self.compress:
            payloads, flags = codecs.compress_batch(raws, codec)
            return [([pay], [fl]) for pay, fl in zip(payloads, flags)]
        return [([raw], [codecs.RAW]) for raw in raws]

    def encode_batch_scalar(self, chunks, codec):
        out = []
        for chunk in chunks:
            raw = chunk.tobytes()
            if self.compress:
                out.append(codecs.compress_block(raw, codec))
            else:
                out.append((raw, codecs.RAW))
        return [([pay], [fl]) for pay, fl in out]

    def fetched_payloads(self, block, view):
        return (0,)

    def decode_batch(self, blocks, view, codec):
        if not blocks:
            return []
        raws = codecs.decompress_batch(
            [b.payloads[0] for b in blocks], [b.flags[0] for b in blocks],
            codec, [b.padded_elems * 2 for b in blocks],
        )
        outs = [np.frombuffer(raw, dtype=np.uint16)[: b.valid_elems]
                for raw, b in zip(raws, blocks)]
        if view.is_full:
            return [np.asarray(o) for o in outs]
        # Host-side precision conversion: one vectorized pass over the batch.
        flat = reconstruct_u16(np.concatenate(outs), view)
        return _split_like(flat, outs)


class BitplaneLayout(Layout):
    """TRACE bit-plane substrate; plane-aligned fetch, vectorized batches."""

    plane_aligned = True
    uses_codec = True

    # Max elements packed+compressed per encode pass: same cache-residency
    # tradeoff as SLAB_ELEMS on the decode side, but encode temporaries
    # (the (16, n) bit matrix) are larger, so groups split on block
    # boundaries past this budget.
    ENCODE_SLAB_ELEMS = 128 * 1024

    def __init__(self, kv_transform: bool = True):
        self.kv_transform = kv_transform
        self.name = "bitplane-kv" if kv_transform else "bitplane"

    @staticmethod
    def _check_sizes(chunks) -> List[int]:
        sizes = [c.size for c in chunks]
        for n in sizes:
            if n % 8:
                raise ValueError(f"block length {n} not a multiple of 8")
        return sizes

    def encode_batch(self, chunks, codec):
        if not chunks:
            return []
        sizes = self._check_sizes(chunks)
        if len(chunks) > 1 and sum(sizes) > self.ENCODE_SLAB_ELEMS:
            out, cur, cur_n = [], [], 0
            for c in chunks:
                if cur and cur_n + c.size > self.ENCODE_SLAB_ELEMS:
                    out.extend(self._encode_slab(cur, codec))
                    cur, cur_n = [], 0
                cur.append(c)
                cur_n += c.size
            out.extend(self._encode_slab(cur, codec))
            return out
        return self._encode_slab(chunks, codec)

    def _encode_slab(self, chunks, codec):
        """One pack + ONE compress_slab for every (plane, block) stream.

        Blocks are padded to a byte multiple, so their plane streams
        concatenate cleanly: packing the concatenation and slicing per
        block is byte-identical to packing each block alone.  The packed
        plane matrix is handed to the codec as a flat slab with (start,
        end) stream bounds — no per-stream bytes are materialized.
        """
        from ..kernels.bitplane import pack_planes_slab

        sizes = [c.size for c in chunks]
        with obs.span("tier.pack"):
            planes = pack_planes_slab(np.concatenate(chunks)
                                      if len(chunks) > 1 else chunks[0].ravel())
        offs = np.cumsum([0] + [n // 8 for n in sizes])
        nblk = len(chunks)
        n8 = planes.shape[1]
        base = np.arange(BF16_BITS, dtype=np.int64)[:, None] * n8
        payloads, flags = codecs.compress_slab(
            planes.reshape(-1),
            (base + offs[None, :-1]).ravel(),
            (base + offs[None, 1:]).ravel(),
            codec,
        )
        return [
            ([payloads[p * nblk + i] for p in range(BF16_BITS)],
             [flags[p * nblk + i] for p in range(BF16_BITS)])
            for i in range(nblk)
        ]

    def encode_batch_scalar(self, chunks, codec):
        # The original write pipeline: per-block plane pack, per-plane
        # compress_block — O(blocks x planes) python-level calls.
        out = []
        self._check_sizes(chunks)
        for chunk in chunks:
            planes = pack_planes(chunk.ravel())
            payloads, flags = [], []
            for p in range(BF16_BITS):
                pay, fl = codecs.compress_block(planes[p].tobytes(), codec)
                payloads.append(pay)
                flags.append(fl)
            out.append((payloads, flags))
        return out

    def fetched_payloads(self, block, view):
        return view.fetched_planes()

    # Max elements decoded per vectorized pass: big enough to amortize the
    # per-call numpy overhead across many 4 KB blocks, small enough that
    # plane/bit temporaries stay cache-resident (the win over per-block
    # decode evaporates once working sets spill to DRAM).
    SLAB_ELEMS = 64 * 1024

    def decode_batch(self, blocks, view, codec):
        if len(blocks) > 1:
            # split into cache-sized slabs on block boundaries
            slabs, cur, cur_elems = [], [], 0
            for b in blocks:
                if cur and cur_elems + b.padded_elems > self.SLAB_ELEMS:
                    slabs.append(cur)
                    cur, cur_elems = [], 0
                cur.append(b)
                cur_elems += b.padded_elems
            slabs.append(cur)
            if len(slabs) > 1:
                out = []
                for s in slabs:
                    out.extend(self.decode_batch(s, view, codec))
                return out
        if not blocks:
            return []
        plane_set = view.fetched_planes()
        nbytes = [b.padded_elems // 8 for b in blocks]
        total = sum(nbytes)
        # Per plane: join the batch's decompressed byte streams, then one
        # subset-unpack for the whole slab (unfetched planes read as zero).
        rows = np.stack([
            np.frombuffer(
                b"".join(codecs.decompress_batch(
                    [b.payloads[p] for b in blocks],
                    [b.flags[p] for b in blocks], codec, nbytes,
                )),
                dtype=np.uint8,
            )
            for p in plane_set
        ])
        flat = unpack_planes_subset(rows, plane_set, total * 8)
        segs: List[Optional[np.ndarray]] = []
        off = 0
        kv_groups: Dict[tuple, List[int]] = {}
        for bi, b in enumerate(blocks):
            seg = flat[off * 8 : off * 8 + b.valid_elems]
            off += nbytes[bi]
            if b.kv_meta is not None:
                m = b.kv_meta
                kv_groups.setdefault((m.n_tokens, m.n_channels), []).append(bi)
                seg = seg[: m.n_tokens * m.n_channels]
            segs.append(seg)
        # Invert the exponent-delta FIRST: guard-bit rounding may carry from
        # mantissa into the exponent, which is only meaningful in the
        # real-exponent domain (not the zigzag-delta domain).  Same-shape
        # windows invert as one vectorized pass.
        for (_, _), idxs in kv_groups.items():
            metas = [blocks[i].kv_meta for i in idxs]
            inv = kv_inverse_batch(np.stack([segs[i] for i in idxs]), metas)
            for i, tok in zip(idxs, inv):
                segs[i] = tok
        if view.is_full:
            return segs
        flat = reconstruct_u16(np.concatenate([s.ravel() for s in segs]), view)
        return [r.reshape(s.shape) for r, s in zip(_split_like(flat, segs), segs)]


def _intersect_views(a: PrecisionView, b: PrecisionView) -> PrecisionView:
    """The widest view whose fetched planes are a subset of both ``a``'s
    and ``b``'s.  Kept planes are the narrower cut; guard planes are
    whatever of the narrower fetch frontier remains beyond it.  This is
    how a read against a truncated block is served: the host gets
    exactly the planes that still physically exist, reconstructed with
    the same guard-rounding rule as a plane-aligned fetch at that view.
    """
    if a == b:
        return a
    r_e = min(a.r_e, b.r_e)
    d_e = min(a.r_e + a.d_e, b.r_e + b.d_e) - r_e
    r_m = min(a.r_m, b.r_m)
    d_m = min(a.r_m + a.d_m, b.r_m + b.d_m) - r_m
    for v in (a, b):
        if (v.r_e, v.d_e, v.r_m, v.d_m) == (r_e, d_e, r_m, d_m):
            return v
    return PrecisionView(r_e=r_e, r_m=r_m, d_e=d_e, d_m=d_m,
                         name=f"cut{1 + r_e + r_m}")


def _split_like(flat: np.ndarray, segs: Sequence[np.ndarray]) -> List[np.ndarray]:
    out, off = [], 0
    for s in segs:
        out.append(flat[off : off + s.size])
        off += s.size
    return out


LAYOUTS = {
    "word": lambda: WordLayout(compress=False),
    "word-comp": lambda: WordLayout(compress=True),
    "bitplane": lambda: BitplaneLayout(kv_transform=False),
    "bitplane-kv": lambda: BitplaneLayout(kv_transform=True),
}


# ---------------------------------------------------------------------------
# Async submission — tickets over a bounded in-flight window
# ---------------------------------------------------------------------------

class Ticket:
    """Handle to one request submitted through :meth:`TierStore.submit_async`.

    Posted writes complete at submission, so their tickets are born done.
    Read tickets complete when the scheduler flushes their in-flight group
    (window overflow, hazard fence, :meth:`wait` or :meth:`drain`).
    ``wait()`` is idempotent: it forces execution of the queue prefix up to
    this ticket on first call and returns the same :class:`Receipt` (or
    re-raises the same error) on every call.
    """

    __slots__ = ("request", "_store", "_receipt", "_error")

    def __init__(self, store: "TierStore", request: Request):
        self._store = store
        self.request = request
        self._receipt: Optional[Receipt] = None
        self._error: Optional[BaseException] = None

    @property
    def done(self) -> bool:
        return self._receipt is not None or self._error is not None

    def _complete(self, receipt: Receipt):
        self._receipt = receipt

    def _fail(self, error: BaseException):
        self._error = error

    def wait(self) -> Receipt:
        if not self.done:
            self._store._flush_through(self)
        if self._error is not None:
            raise self._error
        assert self._receipt is not None
        return self._receipt

    def __repr__(self):
        state = ("done" if self._receipt is not None
                 else "failed" if self._error is not None else "pending")
        return f"Ticket({self.request.key!r}, {state})"


# ---------------------------------------------------------------------------
# TierStore — the host↔device boundary
# ---------------------------------------------------------------------------

class TierStore:
    """A tier device: a :class:`Layout` + codec behind a batched request API.

    ``submit`` is the only real entry point; the legacy tensor/KV methods
    are shims over it.  All traffic lands in per-request receipts, which
    also roll up into ``self.stats``.
    """

    name = "tier"

    def __init__(self, layout: Union[Layout, str] = "word",
                 codec: str = "lz4", block_elems: int = BLOCK_ELEMS,
                 index_cache_entries: int = 4096, kv_window: int = 64,
                 link_model: LinkModel = LinkModel(), window: int = 64,
                 batched_encode: bool = True,
                 sanitize: Optional[bool] = None, device_id: int = 0):
        self.layout = LAYOUTS[layout]() if isinstance(layout, str) else layout
        self.codec = codecs.resolve_codec(codec)
        self.block_elems = block_elems
        self.kv_window = kv_window
        self.link_model = link_model
        self.device_id = device_id           # fleet position (receipts carry it)
        self.window = window                 # max queued (in-flight) reads
        self.batched_encode = batched_encode  # False: scalar reference path
        # Runtime invariant sanitizer: explicit flag wins, else the
        # TRACE_SANITIZE env var ("" / "0" = off).  See _Sanitizer.
        if sanitize is None:
            sanitize = os.environ.get("TRACE_SANITIZE", "").strip() \
                not in ("", "0")
        self.sanitize = bool(sanitize)
        self._san = _Sanitizer(self) if self.sanitize else None
        self.stats = (_MirroredStats(self._san.shadow) if self._san
                      else DeviceStats())
        # Physical-footprint residency ledger: one entry per stored key,
        # equal to that key's stored payload+index bytes at all times.
        self._ledger: Dict[str, ResidencyEntry] = {}
        self._tensors: Dict[str, List[_Block]] = {}
        self._shapes: Dict[str, tuple] = {}
        self._kv_staging: Dict[str, list] = {}   # stream → [token rows]
        self._kv_channels: Dict[str, int] = {}
        self._index = _IndexCache(index_cache_entries)
        self._queue: List[Ticket] = []       # pending read tickets, FIFO
        # Device-global busy clock: host-time `now` plus per-pipe busy
        # frontiers.  Posted writes and window-overflow flushes advance the
        # frontiers without advancing `now`, so LATER groups queue behind
        # their residual occupancy (cross-group contention pricing); waits
        # (sync reads, drain, Ticket.wait) advance `now` to delivery.
        self._now_s = 0.0
        self._ddr_free_s = 0.0
        self._link_free_s = 0.0

    # -- validation (shared by submit / submit_async) -------------------------
    def _validate(self, requests: Sequence[Request]):
        """Reject a malformed batch BEFORE mutating any device state, so a
        bad request cannot leave committed blocks unaccounted.  Reads may
        target any key written anywhere in the same batch: writes drain
        before reads regardless of listed order."""
        written = {req.key for req in requests if isinstance(req, WriteReq)}
        for req in requests:
            if isinstance(req, WriteReq):
                if req.kind not in (TENSOR, KV):
                    raise ValueError(f"unknown request kind {req.kind!r}")
            elif isinstance(req, ReadReq):
                if (req.kind == KV and self.layout.kv_transform
                        and req.view.r_e != EXP_BITS):
                    raise ValueError(
                        "KV views must keep the full (delta) exponent"
                    )
                if (req.key not in self._tensors
                        and not self._kv_staging.get(req.key)
                        and req.key not in written):
                    raise KeyError(req.key)
            elif isinstance(req, GatherReq):
                if req.kind not in (TENSOR, KV):
                    raise ValueError(f"unknown request kind {req.kind!r}")
                if req.k < 0:
                    raise ValueError(f"gather k must be >= 0, got {req.k}")
                digest = np.asarray(req.digest)
                if digest.ndim != 1 or digest.size == 0:
                    raise ValueError(
                        "gather digest must be a flat (channels,) vector"
                    )
                if (req.views is not None
                        and len(req.views) != len(req.keys)):
                    raise ValueError(
                        f"gather views ({len(req.views)}) must align "
                        f"with keys ({len(req.keys)})"
                    )
                kv_exp = req.kind == KV and self.layout.kv_transform
                for view in (req.score_view,) + tuple(req.views or ()):
                    if kv_exp and view.r_e != EXP_BITS:
                        raise ValueError(
                            "KV views must keep the full (delta) exponent"
                        )
                for key in req.keys:
                    if (key not in self._tensors
                            and not self._kv_staging.get(key)
                            and key not in written):
                        raise KeyError(key)
                    c = self._kv_channels.get(key)
                    if c is not None and c != digest.size:
                        raise ValueError(
                            f"gather digest has {digest.size} channels "
                            f"but {key!r} stores {c}"
                        )
            else:
                raise TypeError(f"not a tier request: {req!r}")

    def validate(self, requests: Sequence[Request]):
        """Public batch validation — same checks :meth:`submit` runs before
        touching device state.  A sharding front-end pre-flights every
        shard's sub-batch through this so a malformed fleet batch rejects
        before ANY shard commits (single-device atomicity, fleet-wide)."""
        self._validate(requests)

    # -- sanctioned accounting helpers (lint rule R3) -------------------------
    def _apply_receipt(self, rec: Receipt):
        """Fold one receipt into the running aggregate — the only
        sanctioned path for receipt-driven stats mutation (and the
        point where the sanitizer's shadow aggregate stays in step)."""
        rec.device_id = self.device_id
        self.stats.apply(rec)
        if self._san is not None:
            self._san.shadow.apply(rec)

    def _adjust_stored(self, payload: int = 0, raw: int = 0,
                       blocks: int = 0):
        """Capacity delta outside a receipt (deletes, in-place plane
        truncation) — the only sanctioned path for direct capacity
        mutation."""
        self.stats.dram_bytes_stored += payload
        self.stats.raw_bytes_stored += raw
        self.stats.blocks += blocks
        if self._san is not None:
            sh = self._san.shadow
            sh.dram_bytes_stored += payload
            sh.raw_bytes_stored += raw
            sh.blocks += blocks

    def _sanitize_boundary(self, touched: Optional[Set[str]] = None):
        if self._san is not None:
            self._san.boundary(touched)

    # -- batched entry point -------------------------------------------------
    def submit(self, requests: Sequence[Request]) -> List[Receipt]:
        """Execute a request batch; one receipt per request, in order.

        Reads across the batch are decoded together (grouped by precision
        view) so plane unpacking and reconstruction run as a few vectorized
        numpy passes instead of one per 4 KB block.  Any queued async reads
        drain first, so sync callers always observe program order.
        """
        self._validate(requests)
        if self._queue:
            self._flush_queue(len(self._queue), wait=True)
        receipts: List[Receipt] = [None] * len(requests)  # type: ignore
        # Writes execute in order first so reads in the same batch observe
        # them (single-queue device semantics); the batch's writes encode
        # as ONE slab (see _post_writes).
        write_ix = [i for i, r in enumerate(requests)
                    if isinstance(r, WriteReq)]
        written = set(write_ix)
        read_ix = [i for i in range(len(requests)) if i not in written]
        if write_ix:
            for i, r in zip(write_ix,
                            self._post_writes([requests[i] for i in write_ix])):
                receipts[i] = r
        if read_ix:
            recs = self._do_reads([requests[i] for i in read_ix])
            # sync reads are one group on the shared pipes; the host blocks
            # on their data, so delivery advances the busy clock
            self._schedule_group(
                recs, [(r.dram_bytes_read, r.link_bytes_out) for r in recs],
                wait=True,
            )
            for i, r in zip(read_ix, recs):
                receipts[i] = r
        return receipts

    # -- async entry point ---------------------------------------------------
    def submit_async(self, requests: Sequence[Request]) -> List[Ticket]:
        """Enqueue a request batch; one :class:`Ticket` per request, in order.

        Writes are posted — committed immediately, in listed order — so
        their tickets are born complete.  Reads enter the bounded in-flight
        window and execute later as coalesced groups (window overflow,
        ``wait``/``drain``, or a write-after-read fence on their key).  As
        in :meth:`submit`, the call's writes post before its reads enqueue,
        which makes ``submit_async`` + :meth:`drain` receipt- and
        byte-identical to one sync ``submit`` of the same batch.
        """
        self._validate(requests)
        writes = [r for r in requests if isinstance(r, WriteReq)]
        # Write-after-read fence: posting a write over queued reads of the
        # same key would let those reads observe data from their future.
        # Flush the queue first (groups are prefixes, so order holds).
        if writes:
            hot = frozenset(w.key for w in writes)
            if any(hot & _req_keys(t.request) for t in self._queue):
                self._flush_queue(len(self._queue), wait=False)
        tickets: Dict[int, Ticket] = {}
        if writes:
            # posted writes accumulate into one encode slab, mirroring how
            # queued reads share one decode slab
            write_ix = [i for i, r in enumerate(requests)
                        if isinstance(r, WriteReq)]
            for i, rec in zip(write_ix, self._post_writes(writes)):
                t = Ticket(self, requests[i])
                t._complete(rec)
                tickets[i] = t
        for i, req in enumerate(requests):
            if i not in tickets:
                if len(self._queue) >= self.window:
                    self._flush_queue(len(self._queue), wait=False)
                t = Ticket(self, req)
                self._queue.append(t)
                tickets[i] = t
        if self._san is not None:
            self._san.check_window()
            self._san.check_clock()
        return [tickets[i] for i in range(len(requests))]

    @property
    def pending(self) -> int:
        """Queued (not yet executed) read requests in the in-flight window."""
        return len(self._queue)

    def drain(self, tickets: Optional[Sequence[Ticket]] = None) -> List[Receipt]:
        """Execute everything still queued and return receipts in order.

        With ``tickets``, returns exactly those tickets' receipts (waiting
        on each); otherwise returns the receipts of the reads that were
        pending at call time.  Re-raises the first failed ticket's error.
        """
        waiting = list(tickets) if tickets is not None else list(self._queue)
        if self._queue:
            self._flush_queue(len(self._queue), wait=True)
        return [t.wait() for t in waiting]

    def _flush_through(self, ticket: Ticket):
        """Execute the queue prefix up to and including ``ticket``."""
        try:
            n = self._queue.index(ticket) + 1
        except ValueError:
            return                       # completed (or failed) elsewhere
        self._flush_queue(n, wait=True)

    def _flush_queue(self, n: int, wait: bool = True):
        """Execute the first ``n`` queued reads as one coalesced group.

        The group goes through the same vectorized batched-read path as a
        sync batch; receipts then get queue-delay / overlap-adjusted
        latency from :meth:`LinkModel.schedule`, including any residual
        pipe backlog from earlier groups (the busy clock).  ``wait`` marks
        flushes the host blocks on (Ticket.wait / drain / sync submit) —
        those advance host time to the group's delivery; window-overflow
        and fence flushes do not, so their occupancy carries forward.  On
        failure every ticket of the group records the error (stats for
        whatever committed stay applied by ``_do_reads``) and the error
        propagates.
        """
        group, self._queue = self._queue[:n], self._queue[n:]
        if not group:
            return
        try:
            recs = self._do_reads([t.request for t in group])
        except BaseException as e:
            for t in group:
                t._fail(e)
            raise
        self._schedule_group(
            recs, [(r.dram_bytes_read, r.link_bytes_out) for r in recs],
            wait=wait,
        )
        for t, r in zip(group, recs):
            t._complete(r)

    # -- busy clock ----------------------------------------------------------
    def _schedule_group(self, recs: List[Receipt],
                        traffic: List[Tuple[int, int]], wait: bool):
        """Price one request group on the shared pipes and advance the
        device-global busy clock.  Receipts get ``queue_delay_s`` /
        ``latency_s`` measured from group issue (= host `now`), INCLUDING
        residual DDR/link occupancy left by earlier groups the host never
        waited for — receipts-sum == DeviceStats is untouched (bytes only).
        """
        if not recs:
            return
        now = self._now_s
        ddr_b = max(self._ddr_free_s - now, 0.0)
        link_b = max(self._link_free_s - now, 0.0)
        times = self.link_model.schedule(traffic, ddr_backlog_s=ddr_b,
                                         link_backlog_s=link_b)
        # Device compute (PNM scoring) is a third resource next to the
        # DDR/link pipes: it extends that request's delivery time but
        # occupies neither pipe, so later groups don't queue behind it.
        for rec, (delay, done) in zip(recs, times):
            rec.queue_delay_s = delay
            rec.latency_s = done + rec.device_compute_s
        lm = self.link_model
        self._ddr_free_s = now + lm.base_s + ddr_b \
            + sum(t[0] for t in traffic) / lm.ddr_bw
        self._link_free_s = now + lm.base_s + link_b \
            + sum(t[1] for t in traffic) / lm.link_bw
        if wait:
            # host blocked until the last delivery; pipes are drained past
            # this point, so backlogs collapse to zero for the next group
            self._now_s = now + max(r.latency_s for r in recs)

    @property
    def busy_backlog_s(self) -> float:
        """Residual pipe occupancy beyond host `now` (seconds) — how far
        this device's DDR/link frontiers run ahead of the host clock.
        Zero on an idle device.  The sanctioned readout fleet placement
        uses to fan replicated reads out to the least-busy replica."""
        return max(self._ddr_free_s - self._now_s,
                   self._link_free_s - self._now_s, 0.0)

    def quiesce(self):
        """Idle the host until both device pipes drain.

        Advances host time past every posted write / unwaited flush group,
        so the next request group starts on idle pipes (zero backlog).
        Queued-but-unexecuted reads are NOT forced — use :meth:`drain`.
        """
        self._now_s = max(self._now_s, self._ddr_free_s, self._link_free_s)
        if self._san is not None:
            self._san.check_clock()

    # -- write path ----------------------------------------------------------
    def _post_write(self, req: WriteReq) -> Receipt:
        """Post one write (single-request convenience over _post_writes)."""
        return self._post_writes([req])[0]

    def _post_writes(self, reqs: Sequence[WriteReq]) -> List[Receipt]:
        """Post a batch of writes as ONE encode slab — the single posting
        path shared by ``submit`` and ``submit_async``, so the sync/async
        receipt-identity invariant cannot drift.

        Staging walks the requests in listed order, turning tensors into
        fixed-size blocks and KV rows into transformed windows, but defers
        pack + codec: every staged block lands in one slab that the layout
        encodes in a few vectorized passes (``encode_batch``), mirroring
        how queued reads share one decode slab.  Block commit order — and
        therefore payload bytes, receipts and index entries — is identical
        to encoding each request alone; the differential tests hold the
        batched and scalar pipelines to byte-identity.

        Writes are *posted* (CXL.mem semantics): they occupy the pipes but
        the host does not wait, so their receipts carry schedule latency
        while the busy-clock frontier advances past host `now`.
        """
        recs = [Receipt(key=r.key, op="write", kind=r.kind, tag=r.tag)
                for r in reqs]
        slab = _EncodeSlab()
        try:
            for req, rec in zip(reqs, recs):
                self._stage_write(req, rec, slab)
        finally:
            try:
                # even on a staging failure, everything staged so far must
                # commit — sync semantics committed prior requests' blocks
                self._encode_commit(slab)
            finally:
                lm = self.link_model
                for rec in recs:
                    rec.service_s = lm.latency(rec.dram_bytes_written,
                                               rec.link_bytes_in)
                self._schedule_group(
                    recs,
                    [(r.dram_bytes_written, r.link_bytes_in) for r in recs],
                    wait=False,
                )
                for rec in recs:
                    # whatever was committed stays counted
                    self._apply_receipt(rec)
                self._sanitize_boundary({r.key for r in reqs})
        return recs

    def _stage_write(self, req: WriteReq, rec: Receipt, slab: "_EncodeSlab"):
        data = np.ascontiguousarray(req.data, dtype=np.uint16)
        rec.link_bytes_in += data.size * 2
        if req.kind == TENSOR:
            self._shapes[req.key] = data.shape
            for chunk, valid in iter_blocks(data, self.block_elems):
                slab.add(req.key, rec, chunk, valid)
        else:  # KV (kinds validated in submit)
            rows = data[None, :] if data.ndim == 1 else data
            self._kv_channels[req.key] = rows.shape[-1]
            if not self.layout.kv_transform:
                # Word devices store the token-major stream verbatim in
                # 4 KB blocks — no staging window, no transform.
                for chunk, valid in iter_blocks(rows, self.block_elems):
                    slab.add(req.key, rec, chunk, valid)
            else:
                buf = self._kv_staging.setdefault(req.key, [])
                flat = rows.reshape(-1, rows.shape[-1])
                nrows, i = flat.shape[0], 0
                while i < nrows:
                    if not buf and nrows - i >= self.kv_window:
                        # whole window in one request (page spill, prefill
                        # flush): stage the contiguous slice directly, no
                        # row buffering
                        slab.add(req.key, rec, None,
                                 self.kv_window * flat.shape[1],
                                 kv_window=np.ascontiguousarray(
                                     flat[i : i + self.kv_window]))
                        i += self.kv_window
                        continue
                    take = min(self.kv_window - len(buf), nrows - i)
                    buf.extend(flat[i : i + take])
                    i += take
                    if len(buf) >= self.kv_window:
                        self._stage_kv_window(rec, req.key, slab)
                if req.flush and buf:
                    self._stage_kv_window(rec, req.key, slab)

    def _stage_kv_window(self, rec: Receipt, stream: str,
                         slab: "_EncodeSlab"):
        """Claim the staged window now (ordering is per-stream), but defer
        the exponent-delta transform: it is independent per window, so it
        joins the posting group's batched passes at encode time."""
        buf = self._kv_staging[stream]
        window = np.stack(buf, axis=0)
        buf.clear()  # in place — _stage_write holds a reference to this list
        slab.add(stream, rec, None, window.size, kv_window=window)

    def _encode_commit(self, slab: "_EncodeSlab"):
        """Run the posting group's deferred passes — KV transform, plane
        pack, codec — and commit blocks in staging order."""
        if not slab.chunks:
            return
        with obs.span("tier.encode"):
            with obs.span("tier.kv_transform"):
                self._transform_kv_windows(slab)
            enc = (self.layout.encode_batch if self.batched_encode
                   else self.layout.encode_batch_scalar)
            encoded = enc(slab.chunks, self.codec)
            for (payloads, flags), key, rec, chunk, valid, meta in zip(
                    encoded, slab.keys, slab.recs, slab.chunks, slab.valids,
                    slab.metas):
                self._commit(rec, key,
                             _Block(payloads, flags, valid, chunk.size,
                                    kv_meta=meta))
            slab.clear()

    def _transform_kv_windows(self, slab: "_EncodeSlab"):
        """Resolve deferred KV windows into transformed chunks + metas.

        Batched mode groups same-shape windows through one
        ``kv_forward_batch`` (vectorized modal-exponent + zigzag); the
        scalar reference transforms per window — identical outputs, the
        parity tests compare them.
        """
        pend = [i for i, w in enumerate(slab.kv_windows) if w is not None]
        if not pend:
            return

        def _pad(t: np.ndarray) -> np.ndarray:
            return (np.pad(t, (0, 8 - t.size % 8)) if t.size % 8 else t)

        if not self.batched_encode:
            for i in pend:
                transformed, meta = kv_forward(slab.kv_windows[i])
                slab.chunks[i] = _pad(transformed)
                slab.metas[i] = meta
                slab.kv_windows[i] = None
            return
        groups: Dict[tuple, List[int]] = {}
        for i in pend:
            groups.setdefault(slab.kv_windows[i].shape, []).append(i)
        for shape, idxs in groups.items():
            streams, metas = kv_forward_batch(
                np.stack([slab.kv_windows[i] for i in idxs])
            )
            for i, row, meta in zip(idxs, streams, metas):
                slab.chunks[i] = _pad(row)
                slab.metas[i] = meta
                slab.kv_windows[i] = None

    def _commit(self, rec: Receipt, key: str, block: _Block):
        self._tensors.setdefault(key, []).append(block)
        entry = self._ledger.setdefault(key, ResidencyEntry())
        if self._san is not None:
            self._san.refs.setdefault(key, 1)
        entry.payload_bytes += block.stored_bytes
        entry.index_bytes += INDEX_ENTRY_BYTES
        entry.raw_bytes += block.valid_elems * 2
        entry.blocks += 1
        rec.blocks += 1
        rec.dram_bytes_stored += block.stored_bytes
        rec.dram_bytes_written += block.stored_bytes
        rec.raw_bytes_stored += block.valid_elems * 2
        if self.layout.uses_codec:
            rec.codec_blocks += len(block.flags)
            rec.codec_bypass += sum(
                1 for f in block.flags if f == codecs.RAW)

    def _commit_kv_window(self, rec: Receipt, stream: str):
        """Immediate (non-deferred) window commit for the read path's
        implicit flush and the legacy ``flush_kv`` shim."""
        slab = _EncodeSlab()
        self._stage_kv_window(rec, stream, slab)
        self._encode_commit(slab)

    # -- read path -----------------------------------------------------------
    def _do_reads(self, reqs: Sequence[Request]) -> List[Receipt]:
        # Gather every requested block, tally per-request DRAM/index traffic,
        # then decode per view-group in vectorized passes.  Receipts are
        # applied to the aggregate in a finally so an exception mid-batch
        # cannot desync stats from already-flushed staging windows.
        #
        # Plain reads decode as ONE batched group first; GatherReqs (the
        # PNM top-k path) then execute in listed order — a gather's index
        # accounting depends on its own score-then-winner fetch sequence,
        # so it cannot fold into the shared decode slab.
        recs = [Receipt(key=r.key,
                        op="gather" if isinstance(r, GatherReq) else "read",
                        kind=r.kind, tag=r.tag)
                for r in reqs]
        try:
            read_ix = [i for i, r in enumerate(reqs)
                       if not isinstance(r, GatherReq)]
            if read_ix:
                self._gather_and_decode([reqs[i] for i in read_ix],
                                        [recs[i] for i in read_ix])
            for i, req in enumerate(reqs):
                if isinstance(req, GatherReq):
                    with obs.span("tier.gather"):
                        self._do_gather(req, recs[i])
            return recs
        finally:
            for rec in recs:
                self._apply_receipt(rec)
            touched: Set[str] = set()
            for r in reqs:
                touched |= _req_keys(r)
            self._sanitize_boundary(touched)

    def _do_gather(self, req: GatherReq, rec: Receipt):
        """Execute one PNM gather: score every candidate device-side on
        the ``score_view`` plane subset, then decode full precision for
        the top-k winners only.

        Accounting: the scoring pass reads only the score view's planes
        from DRAM (plus index touches) and ships 4 B/candidate of scores
        over the link; winners are then fetched exactly like plain reads
        at their per-key views, so a gather with ``k >= len(keys)``
        returns byte-identical data to individual :class:`ReadReq`
        submissions.  ``device_compute_s`` prices the scoring kernel at
        :meth:`LinkModel.device_compute` over the scored elements.
        """
        from ..kernels.pnm_score import page_scores_u16, topk_select

        if req.kind == KV:
            for key in req.keys:
                if self._kv_staging.get(key):
                    # implicit flush, accounted to this gather
                    self._commit_kv_window(rec, key)

        def _fetch(keys: Sequence[str], views: Sequence[PrecisionView]
                   ) -> List[np.ndarray]:
            """Plane-aligned fetch + decode of whole keys, tallied into
            ``rec`` — the same per-block walk as ``_gather_and_decode``."""
            per_key_blocks: List[List[_Block]] = []
            per_key_views: List[List[PrecisionView]] = []
            for key, view in zip(keys, views):
                blocks = self._tensors.get(key, [])
                eff = [view if b.view is None
                       else _intersect_views(view, b.view) for b in blocks]
                for i, (b, v) in enumerate(zip(blocks, eff)):
                    self._touch_index(rec, key, i)
                    for p in self.layout.fetched_payloads(b, v):
                        rec.dram_bytes_read += len(b.payloads[p])
                per_key_blocks.append(list(blocks))
                per_key_views.append(eff)
            groups: Dict[PrecisionView, List[_Block]] = {}
            for eff, blocks in zip(per_key_views, per_key_blocks):
                for v, b in zip(eff, blocks):
                    groups.setdefault(v, []).append(b)
            with obs.span("tier.decode"):
                decoded = {
                    v: iter(self.layout.decode_batch(blocks, v, self.codec))
                    for v, blocks in groups.items()
                }
            out = []
            for key, eff in zip(keys, per_key_views):
                segs = [next(decoded[v]) for v in eff]
                out.append(self._assemble(
                    ReadReq(key, kind=req.kind, view=FULL), segs))
            return out

        # --- scoring pass: plane-subset decode feeds the PNM kernel ---
        score_views = [req.score_view] * len(req.keys)
        candidates = _fetch(req.keys, score_views)
        scores = page_scores_u16(candidates, np.asarray(req.digest,
                                                        dtype=np.float32))
        # scores ship to the host: 4 B (f32) per candidate
        rec.link_bytes_out += 4 * len(req.keys)
        rec.device_compute_s += self.link_model.device_compute(
            sum(int(c.size) for c in candidates))

        # --- winner pass: full-precision fetch for the top-k only ---
        winner_ix = topk_select(scores, req.k)
        winner_views = [req.views[i] if req.views is not None else FULL
                        for i in winner_ix]
        winner_keys = [req.keys[i] for i in winner_ix]
        data = _fetch(winner_keys, winner_views)
        if self.layout.plane_aligned:
            # effective per-block views may be truncation-clamped below
            # the request view; recompute the shipped bits per winner
            for key, view, arr in zip(winner_keys, winner_views, data):
                for b in self._tensors.get(key, []):
                    v = view if b.view is None else _intersect_views(view,
                                                                     b.view)
                    n = b.valid_elems
                    if b.kv_meta is not None:
                        n = b.kv_meta.n_tokens * b.kv_meta.n_channels
                    rec.link_bytes_out += n * v.bits // 8
        else:
            rec.link_bytes_out += sum(a.size for a in data) * BF16_BITS // 8
        rec.gather = GatherResult(keys=winner_keys, indices=list(winner_ix),
                                  scores=scores, data=data)
        rec.service_s = rec.latency_s = self.link_model.latency(
            rec.dram_bytes_read, rec.link_bytes_out
        ) + rec.device_compute_s

    def _gather_and_decode(self, reqs: Sequence[ReadReq],
                           recs: List[Receipt]) -> List[Receipt]:
        req_blocks: List[List[_Block]] = []
        req_views: List[List[PrecisionView]] = []
        for req, rec in zip(reqs, recs):
            if req.kind == KV and self._kv_staging.get(req.key):
                # implicit flush, accounted to this request
                self._commit_kv_window(rec, req.key)
            blocks = self._tensors.get(req.key, [])
            if req.block_range is not None:
                lo, hi = req.block_range
                blocks = blocks[lo:hi]
            # A truncated block clamps the request's view to the planes
            # that still exist (per block — blocks committed after the
            # truncation are full again).
            views = [req.view if b.view is None
                     else _intersect_views(req.view, b.view)
                     for b in blocks]
            for off, (b, view) in enumerate(zip(blocks, views)):
                base = (req.block_range[0] if req.block_range else 0) + off
                self._touch_index(rec, req.key, base)
                for p in self.layout.fetched_payloads(b, view):
                    rec.dram_bytes_read += len(b.payloads[p])
            req_blocks.append(list(blocks))
            req_views.append(views)

        # Group all blocks across requests by effective view (the view
        # fixes both the fetched plane set and the reconstruction),
        # decode each group once.
        groups: Dict[PrecisionView, List[_Block]] = {}
        for views, blocks in zip(req_views, req_blocks):
            for view, b in zip(views, blocks):
                groups.setdefault(view, []).append(b)
        with obs.span("tier.decode"):
            decoded = {
                view: iter(self.layout.decode_batch(blocks, view, self.codec))
                for view, blocks in groups.items()
            }

        out: List[Receipt] = []
        for req, rec, blocks, views in zip(reqs, recs, req_blocks,
                                           req_views):
            # per-group iterators advance in encounter order, which is
            # exactly the order the group lists were built in
            segs = [next(decoded[view]) for view in views]
            rec.data = self._assemble(req, segs)
            # Word devices always move full 16-bit containers over the link
            # (paper Issue 2); plane-aligned layouts return the view's bits
            # (the effective, possibly truncation-clamped view per block).
            if self.layout.plane_aligned:
                rec.link_bytes_out += sum(
                    seg.size * view.bits for seg, view in zip(segs, views)
                ) // 8
            else:
                rec.link_bytes_out += rec.data.size * BF16_BITS // 8
            rec.service_s = rec.latency_s = self.link_model.latency(
                rec.dram_bytes_read, rec.link_bytes_out
            )
            out.append(rec)
        return out

    def _assemble(self, req: ReadReq, segs: List[np.ndarray]) -> np.ndarray:
        if not segs:
            return np.empty((0,), dtype=np.uint16)
        if req.kind == KV:
            if segs[0].ndim == 2:           # kv-transformed: (t, C) per window
                return np.concatenate(segs, axis=0)
            flat = np.concatenate(segs)
            C = self._kv_channels.get(req.key, flat.size)
            return flat.reshape(-1, C)
        flat = np.concatenate([s.ravel() for s in segs])
        shape = self._shapes.get(req.key)
        if (req.block_range is None and shape is not None
                and flat.size == int(np.prod(shape))):
            return flat.reshape(shape)
        # ranged reads / multi-write appends return the flat element stream
        return flat

    def _touch_index(self, rec: Receipt, key: str, i: int):
        if self._index.access((key, i)):
            rec.index_hits += 1
        else:
            rec.index_misses += 1
            rec.index_bytes += INDEX_ENTRY_BYTES
            rec.dram_bytes_read += INDEX_ENTRY_BYTES

    # -- introspection -------------------------------------------------------
    def n_blocks(self, key: str) -> int:
        return len(self._tensors.get(key, []))

    def footprint(self, key: str) -> int:
        return sum(b.stored_bytes for b in self._tensors[key])

    def logical_bytes(self, key: str) -> int:
        return sum(b.valid_elems for b in self._tensors[key]) * 2

    # -- residency ledger -----------------------------------------------------
    def resident_bytes(self, prefix: str = "") -> int:
        """Physical bytes this namespace occupies in device DRAM right
        now: stored payload planes plus the 64 B/block index entries.
        An empty prefix sums the whole device.  Equal to the sum of
        stored payload+index bytes at all times (the ledger invariant),
        which makes it the admission-control counterpart of the logical
        :meth:`logical_bytes` projection.  Matching is namespace-
        delimited: ``"r1"`` and ``"r1."`` both mean the ``r1.`` namespace
        (plus the exact key ``r1``) and never claim ``r10.``'s keys.
        Shared (refcounted) pages are counted once however many referers
        hold them."""
        if not prefix:
            return sum(e.physical_bytes for e in self._ledger.values())
        return sum(e.physical_bytes for k, e in self._ledger.items()
                   if _ns_match(k, prefix))

    def compression_ratio(self, prefix: str = "") -> float:
        """Observed logical/physical ratio of one namespace (1.0 when it
        holds nothing) — the feedback signal the ratio-aware admission
        estimator corrects against at every commit boundary.  Namespace-
        delimited like :meth:`resident_bytes`."""
        raw = phys = 0
        for k, e in self._ledger.items():
            if _ns_match(k, prefix):
                raw += e.raw_bytes
                phys += e.physical_bytes
        return raw / phys if phys > 0 else 1.0

    def truncate_planes(self, keys: Sequence[str],
                        view: PrecisionView) -> int:
        """Drop stored planes outside ``view``'s fetched set *in place*,
        reclaiming their payload bytes (paper §III-C: precision scaling
        as a storage knob).

        Each surviving block records the intersection of its previous
        view with ``view``; later reads are served at the intersection
        of the requested and stored views (bit-identical to
        ``reconstruct_u16`` at that view), and their DRAM traffic only
        touches surviving planes.  Staged (uncommitted) KV windows are
        unaffected — blocks committed after a truncation store full
        precision again.  Returns the reclaimed bytes, which reconcile
        exactly with the ledger delta.  Only plane-aligned layouts can
        shed planes of an already-stored block; word layouts store
        opaque compressed containers and raise ``NotImplementedError``.
        Unknown keys are ignored (a cold page may already be deleted).
        Keys with more than one outstanding reference are refused
        (``ValueError``): degrading a shared page would silently change
        what every other referer decodes, breaking their solo-run
        differential — callers must skip shared pages or wait for the
        refcount to drop to one.
        """
        if not self.layout.plane_aligned:
            raise NotImplementedError(
                f"layout {self.layout.name!r} stores word-major "
                "containers; in-place plane truncation needs a "
                "plane-aligned layout"
            )
        for key in keys:
            entry = self._ledger.get(key)
            if entry is not None and entry.refs > 1:
                raise ValueError(
                    f"cannot truncate {key!r}: {entry.refs} references "
                    "hold this shared page"
                )
        # In-flight reads were issued against the current plane mapping;
        # complete them before planes disappear (program order).
        if self._queue:
            self._flush_queue(len(self._queue), wait=True)
        keep = set(view.fetched_planes())
        reclaimed = 0
        for key in keys:
            blocks = self._tensors.get(key)
            if not blocks:
                continue
            freed = 0
            for b in blocks:
                if b.kv_meta is not None and view.r_e != EXP_BITS:
                    raise ValueError(
                        "KV views must keep the full (delta) exponent"
                    )
                for p in range(len(b.payloads)):
                    if p not in keep and b.payloads[p]:
                        freed += len(b.payloads[p])
                        b.payloads[p] = b""
                        b.flags[p] = codecs.RAW
                b.view = (view if b.view is None
                          else _intersect_views(b.view, view))
            if freed:
                self._ledger[key].payload_bytes -= freed
                self._adjust_stored(payload=-freed)
                reclaimed += freed
        self._sanitize_boundary(set(keys))
        return reclaimed

    def refcount(self, key: str) -> int:
        """Outstanding references to ``key`` (0 when not stored)."""
        entry = self._ledger.get(key)
        return entry.refs if entry is not None else 0

    def acquire(self, key: str) -> int:
        """Take one more reference on a stored key (shared-page reuse).

        The caller becomes a co-owner: the stored bytes stay counted once
        in the ledger, and the key survives any single referer's
        :meth:`release` / :meth:`delete` / :meth:`delete_prefix` until
        the last reference retires.  Raises ``KeyError`` for unknown keys
        and ``ValueError`` for truncated ones — a new referer must never
        decode data degraded below what a solo run would have stored.
        Returns the new reference count.
        """
        entry = self._ledger.get(key)
        if entry is None:
            raise KeyError(key)
        if any(b.view is not None for b in self._tensors.get(key, ())):
            raise ValueError(
                f"cannot acquire {key!r}: stored planes were truncated; "
                "a new referer would decode degraded data"
            )
        entry.refs += 1
        if self._san is not None:
            self._san.refs[key] = self._san.refs.get(key, 1) + 1
            self._san.boundary({key})
        return entry.refs

    def release(self, key: str) -> int:
        """Drop one reference; free the stored bytes at zero.

        Returns the remaining reference count.  Raises ``KeyError`` for
        unknown keys — a double release is an accounting bug, not a
        no-op.
        """
        entry = self._ledger.get(key)
        if entry is None:
            raise KeyError(key)
        if entry.refs > 1:
            entry.refs -= 1
            if self._san is not None:
                self._san.refs[key] = self._san.refs.get(key, 1) - 1
                self._san.boundary({key})
            return entry.refs
        # Last reference: in-flight reads were issued against the key's
        # current mapping; complete them before the mapping disappears.
        if self._queue:
            self._flush_queue(len(self._queue), wait=True)
        self._forget(key)
        if self._san is not None:
            self._san.boundary()
            self._san.check_retired(key=key)
        return 0

    def delete(self, key: str):
        entry = self._ledger.get(key)
        if entry is not None and entry.refs > 1:
            # Shared page: deleting means giving up this caller's claim,
            # never yanking bytes out from under the other referers.
            self.release(key)
            return
        # In-flight reads were issued against the key's current mapping;
        # complete them before the mapping disappears.
        if self._queue:
            self._flush_queue(len(self._queue), wait=True)
        self._forget(key)
        if self._san is not None:
            self._san.boundary()
            self._san.check_retired(key=key)

    def _forget(self, key: str, evict_index: bool = True):
        """Drop one key's blocks, staging, shape and index entries,
        returning the stored capacity to the device (queue already
        flushed by the caller).  ``evict_index=False`` lets a namespace
        delete purge the index cache in one pass instead of per key."""
        dropped = self._tensors.pop(key, [])
        if dropped:
            self._adjust_stored(
                payload=-sum(b.stored_bytes for b in dropped),
                raw=-sum(b.valid_elems for b in dropped) * 2,
                blocks=-len(dropped),
            )
        self._ledger.pop(key, None)
        self._shapes.pop(key, None)
        self._kv_staging.pop(key, None)
        self._kv_channels.pop(key, None)
        if self._san is not None:
            self._san.refs.pop(key, None)
        if evict_index:
            self._index.evict_stream(key)

    def delete_prefix(self, prefix: str) -> int:
        """Release every key in one namespace (``.``-delimited match, so
        ``"r1"`` never claims ``r10.``'s keys).

        This is the retirement path of the continuous-batching scheduler:
        a finished request's pages live under a per-request key prefix, and
        one call frees its blocks, staged windows, shapes, KV-channel
        metadata and index-cache entries, returning the stored capacity to
        ``stats`` so the pool can admit queued requests into the headroom.
        Keys other referers still hold (refcount > 1) drop one reference
        and keep their bytes — they free when the last referer retires.
        Queued reads (any stream's) are drained first, exactly like
        :meth:`delete` — per-key program order means the flush cannot
        change any surviving stream's bytes.  Returns the number of keys
        released.  An empty prefix clears the whole device (releasing,
        not force-freeing, shared keys).
        """
        if self._queue:
            self._flush_queue(len(self._queue), wait=True)
        keys = {k for k in self._tensors if _ns_match(k, prefix)}
        keys.update(k for k in self._kv_staging if _ns_match(k, prefix))
        keys.update(k for k in self._kv_channels if _ns_match(k, prefix))
        keys.update(k for k in self._shapes if _ns_match(k, prefix))
        survivors = set()
        for k in keys:
            entry = self._ledger.get(k)
            if entry is not None and entry.refs > 1:
                entry.refs -= 1
                if self._san is not None:
                    self._san.refs[k] = self._san.refs.get(k, 1) - 1
                survivors.add(k)
            else:
                self._forget(k, evict_index=False)
        if not survivors:
            self._index.evict_prefix(prefix)
        else:
            for k in keys - survivors:
                self._index.evict_stream(k)
        if self._san is not None:
            self._san.boundary()
            self._san.check_retired(prefix=prefix, survivors=survivors)
        return len(keys)

    # -- legacy shims (deprecated; forward to submit) ------------------------
    def write_tensor(self, name: str, u16: np.ndarray):
        self.submit([WriteReq(name, u16, kind=TENSOR)])

    def read_tensor(self, name: str, view: PrecisionView = FULL) -> np.ndarray:
        return self.submit([ReadReq(name, kind=TENSOR, view=view)])[0].data

    def write_kv(self, stream: str, tokens_u16: np.ndarray):
        # Matches the historical semantics: full windows commit eagerly,
        # partial tails stay staged until flush_kv / a KV read.
        self.submit([WriteReq(stream, tokens_u16, kind=KV, flush=False)])

    def read_kv(self, stream: str, view: PrecisionView = FULL) -> np.ndarray:
        return self.submit([ReadReq(stream, kind=KV, view=view)])[0].data

    def flush_kv(self, stream: str):
        if self._kv_staging.get(stream):
            # sync entry point: queued reads observe program order (they
            # would otherwise absorb this commit into their own receipts)
            if self._queue:
                self._flush_queue(len(self._queue), wait=True)
            rec = Receipt(key=stream, op="write", kind=KV)
            self._commit_kv_window(rec, stream)
            self._apply_receipt(rec)
            self._sanitize_boundary({stream})


# ---------------------------------------------------------------------------
# Named device configurations (paper Table III)
# ---------------------------------------------------------------------------

class PlainDevice(TierStore):
    """CXL-Plain: word-major, no compression, full-container fetch.

    The named designs default their ``link_model`` overhead to the
    calibrated controller pipeline (``LinkModel.for_design`` — Table V's
    71/84/89-cycle load-to-use anchors); pass ``link_model`` explicitly
    to override with a constant.
    """

    name = "plain"

    def __init__(self, codec: str = "lz4", **kw):
        kw.setdefault("link_model", LinkModel.for_design("plain"))
        super().__init__(layout=WordLayout(compress=False), codec=codec, **kw)


class GCompDevice(TierStore):
    """CXL-GComp: word-major + generic inline 4 KB block compression."""

    name = "gcomp"

    def __init__(self, codec: str = "lz4", **kw):
        kw.setdefault("link_model", LinkModel.for_design("gcomp"))
        super().__init__(layout=WordLayout(compress=True), codec=codec, **kw)


class TraceDevice(TierStore):
    """TRACE: bit-plane substrate + KV transform + plane-aligned fetch."""

    name = "trace"

    def __init__(self, codec: str = "lz4", **kw):
        kw.setdefault("link_model", LinkModel.for_design("trace"))
        super().__init__(layout=BitplaneLayout(kv_transform=True),
                         codec=codec, **kw)


# Compatibility alias: the old common base class.
BaseDevice = TierStore

DEVICE_KINDS = {"plain": PlainDevice, "gcomp": GCompDevice, "trace": TraceDevice}


def make_device(kind: str, shards: Optional[int] = None,
                placement: Optional[str] = None, **kw) -> TierStore:
    """Build a named device — or a fleet of them.

    ``shards`` > 1 returns a :class:`repro.core.sharding.ShardedTierStore`
    over ``shards`` inner devices of this kind (same protocol, so every
    consumer works unchanged).  ``shards=None`` defers to the
    ``TRACE_SHARDS`` env var (the sharded CI suite runs the whole fast
    suite at ``TRACE_SHARDS=4``); pass ``shards=1`` to pin a single
    device regardless — tests that assert single-queue latency shapes do.
    ``placement`` names a policy in ``repro.core.sharding.PLACEMENTS``
    (ignored for a single device).
    """
    if shards is None:
        raw = os.environ.get("TRACE_SHARDS", "").strip()
        shards = int(raw) if raw else 1
    if shards > 1:
        from .sharding import ShardedTierStore
        return ShardedTierStore(shards, kind=kind,
                                placement=placement or "hash-stripe", **kw)
    return DEVICE_KINDS[kind](**kw)
