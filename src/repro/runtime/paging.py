"""Paged KV management over an HBM + CXL(TRACE) tier (paper §II-C, Table II).

KV is managed as fixed-size pages (a window of tokens for all channels of
one layer's K or V).  Pages live in HBM while the hot budget lasts; the
long tail spills to the offload tier (a ``core.tier`` :class:`TierStore`
— Plain, GComp or TRACE).  Page *importance* is long-tailed, so spilled
pages are assigned precision tiers, which a plane-aligned layout serves
with plane-aligned fetch (Mechanism II) — word layouts must always move
full containers (paper Issue 2).

The pool speaks only the TierStore request protocol: spills are
``WriteReq`` submissions, reads are batched ``ReadReq`` submissions (one
``submit`` per layer gather / spill-readback), and every receipt is folded
into per-page traffic counters so attribution is per-page / per-layer
rather than one global stats blob.

The shipped policy mirrors Table II's best row:
    top pages   → BF16 (full, lossless)
    next tier   → ~FP8  (man4 view + guard round: 1+8+4 visible bits)
    cold tail   → ~FP4  (man0 view + guard round: sign+exp only)
KV views keep the full (delta) exponent planes — they are the cheapest,
most compressible planes — and scale mantissa planes only (precision.py).

Physical-footprint accounting + precision-elastic reclamation: eviction
and spill already move *physical* bytes (HBM pages are raw BF16; spilled
pages occupy their post-compression footprint on the device, tracked by
the tier's residency ledger).  ``physical_kv_bytes`` reports the pool's
live physical footprint (HBM + device ledger), and :meth:`reclaim` frees
device bytes *without dropping tokens*: it walks cold spilled pages —
least-recent commit boundary first — applying the next rung of a
configurable degradation ladder of ``PrecisionView`` s via
``TierStore.truncate_planes`` (paper §III-C's in-place plane shedding),
until the requested bytes are reclaimed or the ladder is exhausted.
Word-layout devices cannot shed planes; reclaim then reports 0.

Shared-prefix KV reuse: pools wired to one :class:`PrefixShareIndex`
store identical completed *prompt-prefix* pages once, under a
content-addressed ``shared.`` namespace keyed by a chained token-window
hash (:func:`prefix_chain_hashes`).  The first pool to spill a window
writes it; later pools acquire a refcounted ledger reference instead of
writing (copy-on-write: windows past the token divergence point hash
differently and stay private).  A shared page frees when its last
referer retires, and degrades only while singly-referenced.
"""

from __future__ import annotations

import dataclasses
import hashlib
import warnings
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from .. import obs
from ..core.precision import FULL, MAN0, MAN2, MAN4, PrecisionView
from ..core.tier import (
    GatherReq, KV, ReadReq, Receipt, Ticket, TierStore, WriteReq, make_device,
)


@dataclasses.dataclass(frozen=True)
class PagePolicy:
    """Importance-ranked precision assignment for *spilled* pages."""

    tiers: tuple = ((5, FULL), (3, MAN4), (2, MAN0))   # (count, view) in rank order
    tail_view: PrecisionView = MAN0                     # beyond listed tiers

    def view_for_rank(self, rank: int) -> PrecisionView:
        acc = 0
        for count, view in self.tiers:
            acc += count
            if rank < acc:
                return view
        return self.tail_view

    def avg_bits(self, n_pages: int) -> float:
        if n_pages == 0:
            return 16.0
        return float(
            np.mean([self.view_for_rank(r).bits for r in range(n_pages)])
        )


PAPER_POLICY = PagePolicy()           # Table II: 5×BF16 / 3×FP8 / 2×FP4
LOSSLESS_POLICY = PagePolicy(tiers=((1 << 30, FULL),), tail_view=FULL)


def prefix_chain_hashes(tokens: np.ndarray, page_tokens: int) -> List[str]:
    """Chained content hashes of the leading full token windows.

    ``hashes[w]`` digests tokens ``[0, (w+1)*page_tokens)`` (all batch
    rows), so it names the *entire* prefix up to the end of window ``w``
    — exactly what the KV values of that window are a causal function
    of.  Two requests get equal ``hashes[w]`` iff their prompts agree on
    every token through that window, which is the copy-on-write
    divergence rule: windows past the first differing token chain to
    different digests and stay private.  Only full windows hash; a
    partial tail (or a window containing generated tokens) never
    shares.
    """
    h = hashlib.sha1(str(page_tokens).encode())
    out: List[str] = []
    arr = np.ascontiguousarray(tokens)
    for w in range(arr.shape[-1] // page_tokens):
        h.update(arr[..., w * page_tokens:(w + 1) * page_tokens].tobytes())
        out.append(h.hexdigest()[:16])
    return out


def shared_page_key(share_hash: str, layer: int, kind: str) -> str:
    """Content-addressed device key of one shared page: the chain hash
    names the token prefix, layer/kind select the tensor — every request
    whose prompt contains that prefix computes the same key."""
    return f"shared.{share_hash}.L{layer}.{kind}"


class PrefixShareIndex:
    """Content-addressed index of shared prefix pages on one device.

    Maps a prefix chain hash (see :func:`prefix_chain_hashes`) to the
    ``shared.`` device keys holding that window's KV pages.  Pools
    sharing a device (one :class:`ServeScheduler`'s engines) consult it
    at spill time: the first pool to spill a window writes the page and
    registers it; every later pool with an identical prompt prefix
    *acquires* a reference (``TierStore.acquire``) instead of writing —
    one stored copy, refcounted in the residency ledger, freed when the
    last referer retires.  All pools must serve one model: the hash
    names tokens, and identical tokens only imply identical KV under
    identical params.
    """

    def __init__(self, device: TierStore):
        self.device = device
        # chain hash → {(layer, kind): key}; only live (stored) pages
        self._nodes: Dict[str, Dict[Tuple[int, str], str]] = {}
        self._owner: Dict[str, Tuple[str, Tuple[int, str]]] = {}

    def acquire(self, share_hash: str, layer: int, kind: str) -> Optional[str]:
        """Take a reference on the stored copy of (hash, layer, kind),
        or return None when no pool has stored it yet."""
        key = self._nodes.get(share_hash, {}).get((layer, kind))
        if key is None:
            return None
        self.device.acquire(key)
        return key

    def register(self, share_hash: str, layer: int, kind: str, key: str):
        """Record a freshly written shared page (writer holds the first
        reference via its commit)."""
        self._nodes.setdefault(share_hash, {})[(layer, kind)] = key
        self._owner[key] = (share_hash, (layer, kind))

    def invalidate(self, key: str):
        """Drop a page from the index without releasing it — called
        before a sole-referer page is degraded in place, so no future
        request acquires (and decodes) the truncated copy."""
        owner = self._owner.pop(key, None)
        if owner is None:
            return
        share_hash, slot = owner
        node = self._nodes.get(share_hash)
        if node is not None:
            node.pop(slot, None)
            if not node:
                self._nodes.pop(share_hash, None)

    def release(self, key: str) -> int:
        """Drop one reference; unindex the page when the last retires.
        Returns the remaining reference count."""
        left = self.device.release(key)
        if left == 0:
            self.invalidate(key)
        return left

    def resident_chain(self, hashes: Sequence[str]) -> int:
        """How many *leading* windows of this hash chain have live shared
        pages — the scheduler's novel-KV admission discount."""
        n = 0
        for h in hashes:
            if not self._nodes.get(h):
                break
            n += 1
        return n

# Default precision-elastic degradation ladder: each reclaim rung sheds
# further mantissa planes of cold stored pages in place (Table II's
# BF16 → ~FP8 → ~FP4 progression, applied as a *storage* knob).
DEFAULT_DEGRADE_LADDER = (MAN4, MAN2, MAN0)


@dataclasses.dataclass
class _Page:
    key: str                  # stream id on the device
    layer: int
    kind: str                 # "k" | "v"
    start: int                # first token index
    n_tokens: int
    importance: float = 0.0
    resident: Optional[np.ndarray] = None   # HBM copy (token-major u16) or None
    commit_seq: int = 0       # commit boundary that admitted this page (LRU)
    degrade_level: int = -1   # last degradation-ladder rung applied
    share_hash: Optional[str] = None  # prefix chain hash (shareable window)
    shared_ref: bool = False  # this pool holds a ledger ref on a shared key
    gather_view: Optional[PrecisionView] = None  # frozen PNM winner view


@dataclasses.dataclass
class PageTraffic:
    """Per-page roll-up of the receipts this pool has seen."""

    dram_bytes_read: int = 0
    dram_bytes_written: int = 0
    link_bytes_in: int = 0
    link_bytes_out: int = 0
    index_bytes: int = 0
    device_compute_s: float = 0.0
    requests: int = 0

    def add(self, r: Receipt):
        """Fold one receipt in (field names shared with Receipt)."""
        for f in dataclasses.fields(self):
            if f.name != "requests":
                setattr(self, f.name, getattr(self, f.name) + getattr(r, f.name))
        self.requests += 1

    def merge(self, other: "PageTraffic"):
        for f in dataclasses.fields(self):
            setattr(self, f.name, getattr(self, f.name) + getattr(other, f.name))


class KVPagePool:
    """Per-sequence paged KV with HBM budget + tier spill.

    Host arrays are BF16-as-uint16, token-major ``(tokens, channels)`` —
    exactly the stream a real host would store through CXL.mem.
    """

    # Importance-feedback bookkeeping: scores submitted for keys this
    # pool does not track (see update_importance).
    unknown_importance_keys: int = 0

    def __init__(
        self,
        device: TierStore | str = "trace",
        page_tokens: int = 64,
        hbm_budget_bytes: int = 1 << 30,
        policy: PagePolicy = PAPER_POLICY,
        key_prefix: str = "",
        degrade_ladder: Sequence[PrecisionView] = (),
        sanitize: Optional[bool] = None,
        prefix_index: Optional[PrefixShareIndex] = None,
        strict_importance: bool = False,
    ):
        self.device = (make_device(device, sanitize=sanitize)
                       if isinstance(device, str) else device)
        self.page_tokens = page_tokens
        self.hbm_budget = hbm_budget_bytes
        self.policy = policy
        self.key_prefix = key_prefix        # stream namespace on a shared device
        self.degrade_ladder = tuple(degrade_ladder)
        if prefix_index is not None and prefix_index.device is not self.device:
            raise ValueError(
                "prefix_index must be built on this pool's device — shared "
                "pages are acquired from the device the index registers "
                "them on"
            )
        self.prefix_index = prefix_index
        # Importance-score hygiene (see update_importance): unknown keys
        # are counted (and warned about once); strict mode raises.
        self.strict_importance = strict_importance
        self.unknown_importance_keys = 0
        self._warned_unknown_importance = False
        self._pages: List[_Page] = []
        self._commit_clock = 0              # commit boundaries seen (page LRU)
        self._hbm_used = 0
        self.spill_events: List[_Page] = []   # drained by the serving engine
        self.page_traffic: Dict[str, PageTraffic] = {}
        # key → [Ticket, view-at-issue, Receipt | None]; the receipt slot
        # memoizes exactly-once accounting (see _settle_prefetch)
        self._prefetched: Dict[str, list] = {}
        # I/O latency roll-up from this pool's receipts (simulated seconds).
        self.io_service_s = 0.0       # serialized service time
        self.io_queue_delay_s = 0.0   # time spent queued behind other I/O
        # One page per KV window: the device commits each page's stream in
        # a single transform window.
        self.device.kv_window = page_tokens

    def _account(self, receipts: Sequence[Receipt]):
        for r in receipts:
            self.page_traffic.setdefault(r.key, PageTraffic()).add(r)
            self.io_service_s += r.service_s
            self.io_queue_delay_s += r.queue_delay_s

    def traffic_by_layer(self) -> Dict[int, PageTraffic]:
        """Aggregate per-page traffic up to layers (key format L{n}.*)."""
        out: Dict[int, PageTraffic] = {}
        for p in self._pages:
            t = self.page_traffic.get(p.key)
            if t is not None:
                out.setdefault(p.layer, PageTraffic()).merge(t)
        return out

    # -- write path -----------------------------------------------------------
    def append_page(self, layer: int, kind: str, start: int,
                    tokens_u16: np.ndarray, importance: float = 0.0):
        """Commit one full page (token-major (n, C) uint16)."""
        self.append_pages([(layer, kind, start, tokens_u16, importance)])

    def append_pages(self, pages: Sequence[tuple]):
        """Commit a batch of pages — ``(layer, kind, start, tokens_u16,
        importance)`` each, with an optional sixth ``share_hash`` element
        (see :func:`prefix_chain_hashes`) — with ONE eviction pass at the
        end.

        A commit boundary admits every layer's K and V windows at once;
        batching them turns the resulting spill into one write batch, which
        the device encodes as a single vectorized slab (pack + codec a few
        passes for the whole group) instead of per-page pipelines.

        Share-tagged pages take the content-addressed ``shared.`` key
        instead of this pool's private namespace; residency and eviction
        behave exactly as for private pages (so solo-run differentials
        hold), but the spill write is elided when an identical page is
        already stored — the pool acquires a ledger reference instead.
        """
        with obs.span("commit.append"):
            self._commit_clock += 1
            for entry in pages:
                layer, kind, start, tokens_u16, importance = entry[:5]
                share_hash = entry[5] if len(entry) > 5 else None
                if share_hash is not None and self.prefix_index is not None:
                    key = shared_page_key(share_hash, layer, kind)
                else:
                    share_hash = None
                    key = f"{self.key_prefix}L{layer}.{kind}.{start}"
                page = _Page(key, layer, kind, start, tokens_u16.shape[0],
                             importance=importance,
                             commit_seq=self._commit_clock,
                             share_hash=share_hash)
                # Always admit to HBM first, then evict the least-important
                # pages (possibly this one) — importance, not arrival order,
                # decides residency (paper §II-C: importance is long-tailed).
                page.resident = tokens_u16.copy()
                self._hbm_used += tokens_u16.size * 2
                self._pages.append(page)
            self._rebalance()

    def _rebalance(self):
        """Evict the least-important resident pages when over budget."""
        if self._hbm_used <= self.hbm_budget:
            return
        resident = sorted(
            (p for p in self._pages if p.resident is not None),
            key=lambda p: p.importance,
        )
        writes = []
        fresh_shared: List[_Page] = []
        for p in resident:
            if self._hbm_used <= self.hbm_budget:
                break
            tok = p.resident
            self._hbm_used -= tok.size * 2
            p.resident = None
            self.spill_events.append(p)
            if p.share_hash is not None and self.prefix_index is not None:
                if self.prefix_index.acquire(
                        p.share_hash, p.layer, p.kind) is not None:
                    # Identical page already stored by another referer:
                    # take a ledger reference, skip the spill write.
                    p.shared_ref = True
                    continue
                fresh_shared.append(p)
            writes.append(WriteReq(p.key, tok, kind=KV, flush=True, tag=p.key))
        if writes:
            # Post through the async front-end: spill writes commit eagerly
            # either way, but submit_async leaves queued readback/prefetch
            # tickets in flight instead of forcing them to drain.
            self._account([t.wait() for t in self.device.submit_async(writes)])
        for p in fresh_shared:
            # First writer of this prefix window: the commit's initial
            # reference is this pool's claim; index it for later arrivals.
            self.prefix_index.register(p.share_hash, p.layer, p.kind, p.key)
            p.shared_ref = True

    def update_importance(self, scores: Dict[str, float],
                          strict: Optional[bool] = None):
        """Re-rank pages by externally measured importance (attention
        mass from the serving engine, see ``ServeEngine``'s
        ``importance="attention"`` mode), then rebalance residency.

        Scores for keys this pool does not track (retired pages, typo'd
        namespaces) used to be dropped silently, quietly skewing
        reclamation; they are now counted in
        ``unknown_importance_keys`` and warned about once per pool.
        Strict mode (the ``strict`` argument, defaulting to the pool's
        ``strict_importance`` flag) raises ``KeyError`` instead, so
        stale-key bugs surface at the call site."""
        unknown = [k for k in scores
                   if k not in {p.key for p in self._pages}]
        if unknown:
            self.unknown_importance_keys += len(unknown)
            if self.strict_importance if strict is None else strict:
                raise KeyError(
                    f"importance scores for {len(unknown)} unknown page "
                    f"key(s), e.g. {sorted(unknown)[:3]}"
                )
            if not self._warned_unknown_importance:
                self._warned_unknown_importance = True
                warnings.warn(
                    f"update_importance dropped scores for {len(unknown)} "
                    f"unknown page key(s) (e.g. {sorted(unknown)[:3]}); "
                    "see KVPagePool.unknown_importance_keys",
                    stacklevel=2,
                )
        for p in self._pages:
            if p.key in scores:
                p.importance = scores[p.key]
        self._rebalance()

    # -- read path --------------------------------------------------------------
    def _spill_ranks(self, pages=None) -> Dict[str, int]:
        spilled = sorted(
            (p for p in (pages if pages is not None else self._pages)
             if p.resident is None),
            key=lambda p: -p.importance,
        )
        return {p.key: i for i, p in enumerate(spilled)}

    def read_page(self, page: _Page) -> np.ndarray:
        """One spilled page through the tier at its current policy view."""
        return self.read_pages([page])[0]

    def _page_reqs(self, pages: Sequence[_Page]) -> List[ReadReq]:
        rank = self._spill_ranks()
        return [
            ReadReq(p.key, kind=KV, view=self.policy.view_for_rank(rank[p.key]),
                    tag=p.key)
            for p in pages
        ]

    def read_pages(self, pages: Sequence[_Page]) -> List[np.ndarray]:
        """Batched tier read of spilled pages (one submit for the batch)."""
        receipts = self.device.submit(self._page_reqs(pages))
        self._account(receipts)
        return [r.data for r in receipts]

    def read_pages_async(self, pages: Sequence[_Page]) -> List[Ticket]:
        """Issue spill-readback tickets for ``pages`` without waiting.

        The reads join the device's in-flight window (coalescing with any
        other stream's queued reads) and execute when the window fills or
        :meth:`drain_reads` forces completion — the serving engine calls
        that at the next commit boundary, after the jitted decode step the
        tickets overlapped with.  Views are fixed at issue time from the
        current spill ranks, so a later drain reads the same bytes a sync
        read here would have.
        """
        return self.device.submit_async(self._page_reqs(pages))

    # -- PNM read path: device-side top-k gather -------------------------------
    def _gather_req(self, pages: Sequence[_Page], digest: np.ndarray,
                    k: int) -> GatherReq:
        """Build one :class:`GatherReq` over ``pages``.

        Each candidate's full-precision winner view is FROZEN at its
        first gather — the policy view at the spill ranks of that moment,
        exactly the view the classic readback (:meth:`read_pages`) would
        have issued for the page at its spill boundary.  Later rank
        drift therefore never changes the bytes a winner ships, which is
        what keeps ``k >= len(candidates)`` bit-identical to the full
        readback path across sync/async submission and shard counts."""
        rank = None
        for p in pages:
            if p.gather_view is None:
                if rank is None:
                    rank = self._spill_ranks()
                p.gather_view = self.policy.view_for_rank(rank[p.key])
        return GatherReq(
            keys=tuple(p.key for p in pages),
            digest=np.asarray(digest, dtype=np.float32),
            k=int(k),
            kind=KV,
            views=tuple(p.gather_view for p in pages),
            tag=pages[0].key,
        )

    def gather_topk(self, digest: np.ndarray, k: int,
                    pages: Optional[Sequence[_Page]] = None,
                    ) -> Tuple[List[_Page], List[np.ndarray]]:
        """Device-side top-k over spilled pages — the PNM replacement for
        full spill readback.  ONE ``GatherReq`` scores every candidate on
        the reduced ``score_view`` plane subset against ``digest`` and
        ships full (frozen-view) precision for only the ``k`` winners, so
        link bytes are O(k · page) + one score-plane pass instead of
        O(candidates · page).  Returns ``(winner_pages, data)`` in score
        order; ``k >= len(candidates)`` returns every candidate's exact
        :meth:`read_pages` bytes (tested differential)."""
        cands = [p for p in (pages if pages is not None else self._pages)
                 if p.resident is None]
        if not cands:
            return [], []
        rec = self.device.submit([self._gather_req(cands, digest, k)])[0]
        self._account([rec])
        by_key = {p.key: p for p in cands}
        return [by_key[kk] for kk in rec.gather.keys], rec.gather.data

    def gather_topk_async(self, digest: np.ndarray, k: int,
                          pages: Optional[Sequence[_Page]] = None,
                          ) -> Tuple[List[_Page], Optional[Ticket]]:
        """Issue :meth:`gather_topk` through the async front-end: the
        gather rides the device's in-flight window across the next decode
        step.  Returns ``(candidates, ticket)`` for :meth:`drain_gather`
        (``([], None)`` when nothing is spilled)."""
        cands = [p for p in (pages if pages is not None else self._pages)
                 if p.resident is None]
        if not cands:
            return [], None
        ticket = self.device.submit_async(
            [self._gather_req(cands, digest, k)])[0]
        return cands, ticket

    def drain_gather(self, cands: Sequence[_Page], ticket: Optional[Ticket],
                     ) -> Tuple[List[_Page], List[np.ndarray]]:
        """Wait one gather ticket, fold its receipt into pool traffic,
        and map the winners back to pages → ``(winner_pages, data)``."""
        if ticket is None:
            return [], []
        rec = ticket.wait()
        self._account([rec])
        by_key = {p.key: p for p in cands}
        return [by_key[kk] for kk in rec.gather.keys], rec.gather.data

    def drain_reads(self, tickets: Sequence[Ticket]) -> List[np.ndarray]:
        """Wait on readback tickets, folding receipts into pool traffic.

        If any waited ticket is still queued, this drains the device's
        WHOLE in-flight window, not just these tickets' queue prefix: when
        several streams share one device, the first stream to reach its
        commit boundary flushes every stream's queued reads as one
        coalesced group (cross-stream slab decode, shared-pipe queue-delay
        pricing).  Pools whose tickets were completed by someone else's
        drain just collect receipts without touching the queue — so they
        never prematurely flush tickets issued after theirs.
        """
        if not tickets:
            return []
        if any(not t.done for t in tickets):
            receipts = self.device.drain(tickets)
        else:
            receipts = [t.wait() for t in tickets]
        self._account(receipts)
        return [r.data for r in receipts]

    def prefetch_layer(self, layer: int, kind: str) -> int:
        """Issue async read tickets for (layer, kind)'s spilled pages so a
        following :meth:`read_layer` is served from the in-flight window.
        Returns the number of tickets issued (0 if everything is resident
        or already in flight)."""
        subset = [p for p in self._pages
                  if p.layer == layer and p.kind == kind]
        pages = [p for p in subset
                 if p.resident is None and p.key not in self._prefetched]
        if not pages:
            return 0
        # Rank within the (layer, kind) subset — the same basis read_layer
        # will use — so the issued views match and the prefetch is consumed
        # rather than discarded and re-read.
        rank = self._spill_ranks(subset)
        views = {p.key: self.policy.view_for_rank(rank[p.key]) for p in pages}
        reqs = [ReadReq(p.key, kind=KV, view=views[p.key], tag=p.key)
                for p in pages]
        for p, t in zip(pages, self.device.submit_async(reqs)):
            # entry: [ticket, view_at_issue, receipt-once-accounted]
            self._prefetched[p.key] = [t, views[p.key], None]
        return len(pages)

    def _settle_prefetch(self, entry) -> Receipt:
        """Wait a prefetch ticket, folding its receipt into the pool's
        accounting exactly once (idempotent across settle/consume)."""
        if entry[2] is None:
            entry[2] = entry[0].wait()
            self._account([entry[2]])
        return entry[2]

    def settle_prefetched(self):
        """Account every prefetch ticket the device has already executed.

        A prefetch can be flushed by unrelated traffic (window overflow,
        another stream's sync read) before its ``read_layer`` arrives; its
        bytes are then in ``device.stats`` but not yet in this pool's
        receipts.  Settling keeps the receipts-sum == device-stats
        conservation invariant without forcing pending tickets to execute
        (a still-queued prefetch is counted on neither side).  The settled
        data stays available for a later :meth:`read_layer`.
        """
        for entry in self._prefetched.values():
            if entry[0].done and entry[2] is None:
                self._settle_prefetch(entry)

    def read_layer(self, layer: int, kind: str) -> np.ndarray:
        """Gather all pages of (layer, kind) in token order, applying the
        precision policy to spilled pages (ranked by importance).  Spilled
        pages come from matching prefetch tickets when available; the rest
        go to the device as one request batch."""
        pages = sorted(
            (p for p in self._pages if p.layer == layer and p.kind == kind),
            key=lambda p: p.start,
        )
        rank = self._spill_ranks(pages)
        served: Dict[str, np.ndarray] = {}
        reqs = []
        for p in pages:
            if p.resident is not None:
                continue
            view = self.policy.view_for_rank(rank[p.key])
            pf = self._prefetched.pop(p.key, None)
            if pf is not None:
                rec = self._settle_prefetch(pf)
                if pf[1] == view:
                    served[p.key] = rec.data
                    continue
                # rank drifted since prefetch: traffic stays accounted,
                # data is re-read at the now-correct view
            reqs.append(ReadReq(p.key, kind=KV, view=view, tag=p.key))
        rs = self.device.submit(reqs) if reqs else []
        self._account(rs)
        served.update({r.key: r.data for r in rs})
        out = [p.resident if p.resident is not None else served[p.key]
               for p in pages]
        return np.concatenate(out, axis=0) if out else np.empty((0, 0), np.uint16)

    # -- precision-elastic reclamation -----------------------------------------
    def reclaim(self, target_bytes: int,
                ladder: Optional[Sequence[PrecisionView]] = None) -> int:
        """Reclaim up to ``target_bytes`` of *physical* device bytes by
        shedding mantissa planes of cold spilled pages in place.

        Walks spilled pages coldest-first — least-recent commit boundary,
        then least important — applying one ladder rung per pass
        (``TierStore.truncate_planes``): every cold page degrades one
        step before any page degrades two, so sustained pressure spreads
        precision loss instead of destroying a single page.  A page's
        ladder position is remembered across calls.  Shedding planes is
        lossy and irreversible, so the ladder is strictly opt-in: the
        pool's ``degrade_ladder`` defaults to empty and an explicit
        ladder (e.g. ``DEFAULT_DEGRADE_LADDER``) must be configured for
        reclaim to touch anything.  Returns the bytes actually
        reclaimed (0 when the ladder is empty, nothing is spilled,
        everything is already at the last rung, or the device's layout
        cannot shed planes — word layouts).  HBM-resident pages are
        untouched: they occupy HBM, not device capacity, and keep their
        exact values.

        Shared pages never degrade in place: truncating a co-owned page
        would change what every other referer decodes, and even a
        sole-referer page keeps its content-addressed key — a later
        request re-writing that "fresh" window would append to the
        degraded stream.  The ladder walks private pages only; shared
        pages free whole at the last referer's retirement.  Any prefetch
        ticket issued against a page before its truncation is settled
        and discarded: its data predates the degrade, and serving it
        would break the degraded-decode differential.
        """
        with obs.span("pool.reclaim"):
            ladder = (self.degrade_ladder if ladder is None else tuple(ladder))
            if target_bytes <= 0 or not ladder:
                return 0
            cold = sorted(
                (p for p in self._pages if p.resident is None),
                key=lambda p: (p.commit_seq, p.importance, p.start),
            )
            freed = 0
            for level, view in enumerate(ladder):
                for page in cold:
                    if freed >= target_bytes:
                        return freed
                    if page.degrade_level >= level:
                        continue
                    if page.shared_ref:
                        continue
                    try:
                        freed += self.device.truncate_planes([page.key], view)
                    except NotImplementedError:
                        return freed        # word layout: nothing to shed
                    page.degrade_level = level
                    # truncate_planes drained the queue, so a prefetch issued
                    # earlier has executed against the PRE-truncation planes;
                    # account it, then drop it so read_layer re-reads the
                    # degraded state instead of serving stale full precision.
                    pf = self._prefetched.pop(page.key, None)
                    if pf is not None:
                        self._settle_prefetch(pf)
            return freed

    # -- teardown ---------------------------------------------------------------
    def release(self) -> int:
        """Retire this pool: free every page and tear down its namespace.

        Outstanding prefetch tickets are settled first (their receipts
        fold into this pool's accounting exactly once), then every key the
        pool ever wrote is deleted from the device in one
        :meth:`TierStore.delete_prefix` call — blocks, staged partial
        windows and index entries — so the stored capacity returns to the
        device for the next admitted request.  HBM-resident pages are
        dropped and ``hbm_bytes`` goes to zero.  Returns the number of
        device keys freed.

        The pool's traffic receipts (``page_traffic``, ``io_service_s``)
        survive release — a retired request's accounting is still part of
        the serving record.  Pools sharing a device must use distinct
        prefixes (the scheduler namespaces per request: ``r{id}.``); with
        an EMPTY ``key_prefix`` only this pool's own page keys are
        deleted, never the rest of a shared device.

        Shared pages release their ledger reference instead: the stored
        copy survives as long as any other request still refers to it,
        and frees with the last retirement.
        """
        with obs.span("pool.release"):
            for entry in self._prefetched.values():
                self._settle_prefetch(entry)
            self._prefetched.clear()
            freed = 0
            for p in self._pages:
                if p.shared_ref:
                    self.prefix_index.release(p.key)
                    p.shared_ref = False
                    freed += 1
            if self.key_prefix:
                freed += self.device.delete_prefix(self.key_prefix)
            else:
                keys = {p.key for p in self._pages if not p.share_hash}
                for k in keys:
                    self.device.delete(k)
                freed += len(keys)
            self._pages.clear()
            self.spill_events.clear()
            self._hbm_used = 0
            return freed

    # -- accounting ---------------------------------------------------------------
    @property
    def hbm_bytes(self) -> int:
        return self._hbm_used

    @property
    def device_resident_bytes(self) -> int:
        """Physical bytes this pool's private namespace occupies on the
        device right now (stored payload + index, from the residency
        ledger).  Shared pages live under the device-wide ``shared.``
        namespace and are reported by :attr:`shared_resident_bytes`."""
        return self.device.resident_bytes(self.key_prefix)

    @property
    def shared_resident_bytes(self) -> int:
        """Physical bytes of the shared pages this pool holds references
        on.  Summed per *key*, so two pools referencing one stored copy
        each report its full size — use the device-wide
        ``resident_bytes("shared.")`` for the deduplicated total."""
        return sum(self.device.resident_bytes(p.key)
                   for p in self._pages if p.shared_ref)

    @property
    def physical_kv_bytes(self) -> int:
        """Live physical KV footprint: raw HBM residents + the device
        namespace's post-compression ledger bytes — the quantity a
        physical capacity model admits against, as opposed to the
        logical ``projected_kv_bytes`` projection."""
        return self._hbm_used + self.device_resident_bytes

    @property
    def spilled_pages(self) -> int:
        return sum(1 for p in self._pages if p.resident is None)

    def iter_pages(self) -> Tuple[_Page, ...]:
        """All committed pages in commit order — the public view engines
        and benchmarks rank/gather over.  The returned handles are the
        same objects ``read_pages`` / ``gather_topk`` accept."""
        return tuple(self._pages)

    def stats(self):
        self.settle_prefetched()
        return self.device.stats
