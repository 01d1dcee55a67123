"""Batched serving engine with TRACE-tiered KV offload.

End-to-end path (paper Fig. 1/6 mapped onto a TPU host):

  prefill  — jit'd full-prompt forward fills a jnp KV cache; completed
             pages (window of ``page_tokens``) are committed to the
             ``KVPagePool`` as BF16 token-major streams (the CXL.mem write
             stream of the paper).
  decode   — jit'd single-token step reads the *reconstructed* KV
             (HBM-resident pages exact; spilled pages served by the tier
             device at their policy precision) and appends new tokens.
  accounting — every step tallies bytes on HBM / CXL link / device DRAM
             from the pool's device stats; ``throughput_model()`` converts
             them to a tok/s ceiling with the paper's first-order model.

This engine is intentionally *functional* about the device: KV numerics
flow through the actual bit-plane + codec + precision pipeline, so serving
quality under a policy is measurable, not assumed.

I/O overlap (``async_io``, default on): spill readback goes through the
tier's queued front-end — tickets are issued at the commit boundary and
drained at the *next* one, so they are in flight across the jitted decode
step in between and their receipts carry overlap-adjusted latency instead
of serialized sync latency.  Tier reads are byte-identical either way
(the async queue preserves per-key program order), and under a lossless
policy generation is bit-identical to ``async_io=False`` (tested).  Under
a *lossy* policy the one-boundary deferral is visible: the decode steps
between issue and drain still attend over the pristine HBM values, so
tokens can differ from the serialized engine (freshly spilled pages serve
one extra boundary at full precision — the overlap hides, never adds,
degradation).  Total traffic is identical in all modes.

Multi-stream serving: :class:`MultiStreamEngine` runs N independent
sequences whose page pools share ONE tier device queue (per-stream key
namespaces).  In round-robin steady state every stream's boundary-issued
tickets accumulate in the shared window and the first stream to reach
its next commit boundary drains them as one coalesced cross-stream flush
group (see :meth:`KVPagePool.drain_reads`) — the many-stream sharing the
ROADMAP calls for.

Continuous batching: :class:`ServeScheduler` adds request
arrival/departure on top of that sharing — requests from a synthetic
trace (:func:`repro.core.synth.request_trace`) wait FIFO for a batch
slot, prefill on admission (gated on projected KV capacity), decode
with whoever else is active, and retire at the commit boundary of their
last token, freeing their pages and tier namespace
(:meth:`ServeEngine.retire`) for the next queued request.  For a plain
GQA ``k``/``v`` cache the scheduler's requests hold rows of one
:class:`SlotCache`, and a tick decodes all of them in one jitted step.
Per-sequence outputs stay bit-identical to solo runs under dynamic
membership — the contract every piece of this module preserves.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs
from ..configs.base import ArchConfig
from ..core.system_model import SystemSpec
from ..core.tier import Ticket, TierStore, make_device
from ..models import decode_step, forward, init_cache
from ..models.layers import query_chunk
from .paging import (
    KVPagePool, PagePolicy, PAPER_POLICY, PrefixShareIndex, _Page,
    prefix_chain_hashes, shared_page_key,
)

# One jitted step per distinct (frozen, hashable) ArchConfig, shared by
# every engine — N streams of the same model trace and compile once, not
# N times.
_jit_step = jax.jit(decode_step, static_argnums=0)


def _write_rows(full, rows, row):
    """``rows`` ``(L, b, ...)`` into ``full`` ``(L, R, ...)`` from row
    ``row`` on; ``full`` is donated, so the write is in place."""
    return jax.lax.dynamic_update_slice_in_dim(full, rows, row, 1)


_write_rows_jit = jax.jit(_write_rows, donate_argnums=0)

#: Most tokens of completed KV windows that one device slice brings to the
#: host.  A long prompt's commit pulls in slices of this size: on a v5e
#: host one 2048-token slice of Mistral-7B's K and V (64 MB) took 84-100
#: ms to reach numpy, a 1024-token one 12-21 ms.
_PULL_TOKENS = 1024


def _sample_next(logits: np.ndarray, rng: np.random.Generator,
                 greedy: bool) -> np.ndarray:
    """Next-token ids from last-position logits (one sampling path for
    single- and multi-stream generation)."""
    if greedy:
        return logits.argmax(-1).astype(np.int32)
    p = np.exp(logits - logits.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    return np.array([rng.choice(p.shape[-1], p=row) for row in p], np.int32)


@dataclasses.dataclass
class ServeStats:
    tokens_generated: int = 0
    prefill_tokens: int = 0
    hbm_page_bytes: int = 0
    tier_dram_read: int = 0
    tier_dram_stored: int = 0
    tier_link_out: int = 0
    spilled_pages: int = 0
    kv_logical_bytes: int = 0
    tier_io_service_s: float = 0.0      # serialized service time of all I/O
    tier_io_queue_delay_s: float = 0.0  # queueing on the shared DDR/link pipes
    tier_device_compute_s: float = 0.0  # PNM scoring time on the device

    @property
    def kv_compression_ratio(self) -> float:
        return self.kv_logical_bytes / max(
            self.tier_dram_stored + self.hbm_page_bytes, 1
        )


@functools.lru_cache(maxsize=32)
def slot_layout(cfg: ArchConfig) -> bool:
    """Whether ``cfg``'s decode cache is the plain GQA ``k``/``v`` layout
    whose rows :class:`SlotCache` batches.  A rolling window, leading
    dense layers, a latent (MLA) or recurrent cache keeps one cache per
    request."""
    spec = jax.eval_shape(lambda: init_cache(cfg, 1, 8))
    return set(spec) == {"layers"} and set(spec["layers"]) == {"k", "v"}


class SlotCache:
    """One decode cache of ``rows`` rows shared by a scheduler's requests.

    Each request's engine holds ``batch`` rows from ``row`` on.  A decode
    round stages every decoding row's token and position
    (:meth:`stage`); the round's first :meth:`decode` call runs ONE jitted
    ``decode_step`` over all rows with a ``(rows,)`` position vector, reads
    the rows' last-position logits to the host once, and each call then
    takes its own rows.  Rows that do not decode in a round (free slots,
    requests that just emitted their last token) still compute, at their
    parked position: at or past their valid length, where attention masks
    it and the next prefill or decode overwrites it before any read.  The
    step's shapes never change with the number of active requests, so it
    compiles once per (config, rows, ``max_seq``).
    """

    def __init__(self, cfg: ArchConfig, params, rows: int, max_seq: int,
                 batch: int = 1):
        self.cfg = cfg
        self.params = params
        self.batch = batch
        self.cache = init_cache(cfg, rows, max_seq)
        self.parked = np.zeros((rows,), np.int32)
        self._staged: Dict[int, Tuple[np.ndarray, int]] = {}
        self._ready: Dict[int, np.ndarray] = {}

    @property
    def rows(self) -> int:
        return self.parked.size

    @property
    def max_seq(self) -> int:
        return self.cache["layers"]["k"].shape[2]

    def grow(self, max_seq: int):
        """Lengthen every row to ``max_seq`` positions (zeros past the
        old end), for a scheduler whose sequence budget grew."""
        pad = max_seq - self.max_seq
        if pad > 0:
            self.cache = jax.tree.map(
                lambda a: jnp.pad(a, [(0, 0), (0, 0), (0, pad)]
                                  + [(0, 0)] * (a.ndim - 3)), self.cache)

    def read(self, row: int, kind: str, lo: int = 0,
             n: Optional[int] = None) -> jax.Array:
        """Device slice of ``batch`` rows from ``row`` of the leaf
        ``kind``, token positions ``[lo, lo + n)`` (to the end without
        ``n``)."""
        leaf = self.cache["layers"][kind]
        n = leaf.shape[2] - lo if n is None else n
        start = (0, row, lo) + (0,) * (leaf.ndim - 3)
        size = (leaf.shape[0], self.batch, n) + leaf.shape[3:]
        return jax.lax.dynamic_slice(
            leaf, [np.int32(i) for i in start], size)

    def view(self, row: int) -> dict:
        """The rows of one request as a cache of their own."""
        return {"layers": {k: self.read(row, k)
                           for k in self.cache["layers"]}}

    def write(self, row: int, layers: dict):
        """Write the leaves of ``layers`` (device or host, ``(L, batch,
        max_seq, ...)``) into the rows from ``row``, one jitted in-place
        row write per leaf."""
        out = dict(self.cache["layers"])
        for kind, rows in layers.items():
            out[kind] = _write_rows_jit(out[kind], rows, np.int32(row))
        self.cache = {"layers": out}

    def park(self, row: int, pos: int):
        """Where the rows from ``row`` compute in a step they do not
        decode in: at their valid length ``pos``."""
        self.parked[row : row + self.batch] = pos

    def release(self, row: int):
        self.park(row, 0)
        self._staged.pop(row, None)
        self._ready.pop(row, None)

    def stage(self, row: int, tokens: np.ndarray, pos: int):
        """Queue the rows from ``row`` to decode ``tokens`` (``(batch,
        1)``) at ``pos`` in the next step."""
        self._staged[row] = (np.asarray(tokens, np.int32).reshape(-1, 1), pos)

    def decode(self, row: int, tokens: np.ndarray, pos: int) -> np.ndarray:
        """The rows' next-token logits ``(batch, vocab)`` after decoding
        ``tokens`` at ``pos``: from the step this round already ran, or
        from a step run now over every staged row."""
        if row not in self._ready:
            self.stage(row, tokens, pos)
            self._step()
        return self._ready.pop(row)

    def _step(self):
        b = self.batch
        tokens = np.zeros((self.rows, 1), np.int32)
        for row, (tok, pos) in self._staged.items():
            tokens[row : row + b] = tok
            self.park(row, pos)
        batch = {"tokens": jnp.asarray(tokens),
                 "cache_pos": jnp.asarray(self.parked)}
        with obs.span("model.decode"):
            logits, self.cache = _jit_step(self.cfg, self.params, batch,
                                           self.cache)
        obs.count("model.decode_batches")
        obs.count("model.decode_rows", len(self._staged) * b)
        with obs.span("model.sync"):
            host = np.asarray(logits[:, -1])
        for row, (_, pos) in self._staged.items():
            self._ready[row] = host[row : row + b]
            self.park(row, pos + 1)
        self._staged.clear()


class ServeEngine:
    """Single-host serving of one model with paged, tiered KV.

    The engine owns a batch-``batch`` cache, or, given ``slots``, the
    rows of a :class:`SlotCache` from ``row`` on; every read and write
    of its KV goes through :meth:`_kv` and :meth:`_put_kv`."""

    def __init__(
        self,
        cfg: ArchConfig,
        params,
        *,
        max_seq: int = 512,
        batch: int = 1,
        page_tokens: int = 64,
        hbm_kv_budget: int = 1 << 22,
        device_kind: Union[str, TierStore] = "trace",
        policy: PagePolicy = PAPER_POLICY,
        key_prefix: str = "",
        async_io: bool = True,
        sanitize: Optional[bool] = None,
        prefix_index: Optional[PrefixShareIndex] = None,
        pnm_topk: Optional[int] = None,
        importance: str = "recency",
        slots: Optional[SlotCache] = None,
        row: int = 0,
    ):
        assert not cfg.is_encoder_only, "serving needs a decoder"
        if importance not in ("recency", "attention"):
            raise ValueError(f"unknown importance mode {importance!r}")
        if pnm_topk is not None and pnm_topk < 0:
            raise ValueError("pnm_topk must be >= 0 (or None to disable)")
        self.cfg = cfg
        self.params = params
        self.batch = batch
        self.max_seq = max_seq
        self.page_tokens = page_tokens
        self.async_io = async_io
        # PNM read mode: spill readback becomes a device-side top-k
        # gather (one GatherReq per KV kind per boundary) — only the k
        # highest-scoring spilled pages ship back.  k >= spilled pages
        # degenerates to the full readback bit-for-bit.
        self.pnm_topk = pnm_topk
        # "recency" keeps the pre-existing commit-order ranking;
        # "attention" accumulates digest-proxy attention mass per page
        # each commit boundary and feeds pool.update_importance.
        self.importance = importance
        self._imp_acc: Dict[str, float] = {}
        self.pool = KVPagePool(
            device_kind, page_tokens, hbm_kv_budget, policy,
            key_prefix=key_prefix, sanitize=sanitize,
            prefix_index=prefix_index,
        )
        self.slots, self.row = slots, row
        self.cache = None if slots is not None else init_cache(cfg, batch, max_seq)
        self.pos = 0
        # Prompt-prefix chain hashes (share-tagging completed prompt
        # windows); filled by the first prefill when the pool is wired to
        # a PrefixShareIndex, empty otherwise.
        self._share_hashes: List[str] = []
        self._prompt_len = 0
        self._inflight: List[Tuple[_Page, Ticket]] = []
        self._inflight_gathers: List[Tuple[List[_Page], Ticket]] = []
        self._decode = lambda p, b, c: _jit_step(cfg, p, b, c)
        self._prefill = self._decode
        # the request tag of this engine's spans (``obs.span``'s ``req``):
        # its key namespace, ``r<id>`` under the scheduler
        self.tag = key_prefix.rstrip(".") or None

    # -- helpers ---------------------------------------------------------------
    def _kinds(self) -> List[str]:
        """The leaf names of the KV cache's ``layers``."""
        src = self.cache if self.slots is None else self.slots.cache
        return list(src.get("layers", {}))

    def _kv(self, kind: str, lo: int = 0, n: Optional[int] = None):
        """This engine's KV leaf ``kind`` on the device, ``(L, batch,
        n, ...)`` for token positions ``[lo, lo + n)``, all of them
        without ``n``."""
        if self.slots is not None:
            return self.slots.read(self.row, kind, lo, n)
        leaf = self.cache["layers"][kind]
        if n is None:
            return leaf
        return jax.lax.dynamic_slice_in_dim(leaf, np.int32(lo), n, axis=2)

    def _put_kv(self, layers: Dict[str, np.ndarray]):
        """Replace this engine's leaves ``layers`` (host, whole rows)."""
        for v in layers.values():
            obs.count("commit.h2d_bytes", v.nbytes)
        if self.slots is not None:
            self.slots.write(self.row, layers)
            return
        self.cache = dict(self.cache)
        self.cache["layers"] = dict(self.cache["layers"])
        for k, v in layers.items():
            self.cache["layers"][k] = jnp.asarray(v)

    def _commit_pages(self, lo: int, hi: int):
        """Push completed KV windows [lo, hi) into the page pool."""
        with obs.span("commit.pages", self.tag):
            # Tickets issued at the previous boundary were in flight across the
            # decode step that just ran — apply their data before committing.
            self.flush_io()
            kinds = self._kinds()
            kv_keys = [k for k in ("k", "v", "c_kv") if k in kinds]
            if not kv_keys:
                return  # SSM/hybrid: constant-size state, nothing paged
            pt = self.page_tokens
            starts = [s for s in range(lo - lo % pt, hi, pt) if s + pt <= hi]
            # Gather every completed window across layers and kinds into one
            # batched admission: the spill this triggers goes to the device as
            # one write batch → one vectorized encode slab, instead of a
            # per-page pack+codec pipeline.
            batch_pages = []
            per_pull = max(_PULL_TOKENS // pt, 1)
            for g in range(0, len(starts), per_pull):
                group = starts[g : g + per_pull]
                # only the completed windows come to the host, not the
                # whole cache: one device slice per kind, whose size (and
                # so its compiled program) depends on the window count alone
                lo_tok, n_tok = group[0], group[-1] + pt - group[0]
                bufs = {}
                with obs.span("commit.pull"):
                    for kind in kv_keys:
                        # (L, B, n_tok, ...) bf16
                        bufs[kind] = np.asarray(self._kv(kind, lo_tok, n_tok))
                        obs.count("commit.d2h_bytes", bufs[kind].nbytes)
                for start in group:
                    for kind in kv_keys:
                        buf = bufs[kind]
                        n_layers = buf.shape[0]
                        # Windows fully inside the prompt carry their prefix
                        # chain hash: identical prompt prefixes produce
                        # identical KV there (causal attention), so these
                        # pages are the shareable ones.  Windows touching
                        # generated tokens stay private — that is the
                        # copy-on-write divergence point.
                        share = None
                        if (self._share_hashes
                                and start + pt <= self._prompt_len):
                            share = self._share_hashes[start // pt]
                        for layer in range(n_layers):
                            page = buf[layer, :, start - lo_tok : start - lo_tok + pt]
                            tok = page.reshape(pt * self.batch, -1)
                            u16 = np.ascontiguousarray(tok).view(np.uint16)
                            # recency as default importance;
                            # importance="attention" replaces it below with
                            # accumulated attention mass and keeps re-ranking
                            # live pages via pool.update_importance every
                            # boundary
                            batch_pages.append(
                                (layer, kind, start, u16, float(start), share)
                            )
            if batch_pages:
                if self.importance == "attention":
                    batch_pages = self._apply_attention_importance(batch_pages)
                # the pulled buffers belong to this commit alone, so the
                # pool keeps its pages as views of them
                self.pool.append_pages(batch_pages, copy=False)
            self._issue_readback()

    def _issue_readback(self):
        """Start spill readback for this boundary's evictions.

        Sync mode reads and applies immediately (the pre-async behavior).
        Async mode only issues tickets: they ride the device's in-flight
        window across the next jitted decode step and are drained/applied
        by :meth:`flush_io` at the next commit boundary — decode and tier
        fetch overlap instead of serializing.
        """
        events, self.pool.spill_events = self.pool.spill_events, []
        if not events:
            return
        with obs.span("commit.issue"):
            if self.pnm_topk is not None:
                self._issue_gather(events)
                return
            if self.async_io:
                self._inflight.extend(
                    zip(events, self.pool.read_pages_async(events))
                )
            else:
                self._apply_readback(events, self.pool.read_pages(events))

    def _issue_gather(self, events: Sequence[_Page]):
        """PNM read mode: replace the boundary's full spill readback with
        one device-side top-k gather per KV kind.

        The device scores every candidate page on the reduced
        ``score_view`` plane subset against this step's query digest and
        ships full precision for only the ``pnm_topk`` winners; losers
        keep their pristine HBM values in the jnp cache (the overlap
        contract: PNM hides degradation, never adds it).  With
        ``pnm_topk >= len(events)`` every candidate wins and the applied
        bytes are identical to the classic readback."""
        by_kind: Dict[str, List[_Page]] = {}
        for p in events:
            by_kind.setdefault(p.kind, []).append(p)
        for kind, pages in by_kind.items():
            digest = self._query_digest(kind)
            if self.async_io:
                cands, ticket = self.pool.gather_topk_async(
                    digest, self.pnm_topk, pages)
                if ticket is not None:
                    self._inflight_gathers.append((cands, ticket))
            else:
                winners, data = self.pool.gather_topk(
                    digest, self.pnm_topk, pages)
                self._apply_readback(winners, data)

    def flush_io(self):
        """Drain in-flight readback tickets and fold them into the cache."""
        if not self._inflight and not self._inflight_gathers:
            return
        inflight, self._inflight = self._inflight, []
        gathers, self._inflight_gathers = self._inflight_gathers, []
        if inflight:
            pages = [p for p, _ in inflight]
            with obs.span("commit.drain", self.tag):
                data = self.pool.drain_reads([t for _, t in inflight])
            self._apply_readback(pages, data)
        for cands, ticket in gathers:
            with obs.span("commit.drain", self.tag):
                winners, data = self.pool.drain_gather(cands, ticket)
            self._apply_readback(winners, data)

    def _query_digest(self, kind: str) -> np.ndarray:
        """f32 mean of the last committed window's rows for ``kind`` —
        the host-side stand-in for the current query direction that both
        the PNM gather and attention-mass importance score against."""
        buf = np.asarray(self._kv(kind))
        channels = int(np.prod(buf.shape[3:])) if buf.ndim > 3 else 1
        lo = max(0, self.pos - self.page_tokens)
        win = buf[:, :, lo:self.pos]
        if win.size == 0:
            return np.zeros((channels,), np.float32)
        return win.astype(np.float32).reshape(-1, channels).mean(axis=0)

    def _attention_masses(self) -> Dict[Tuple[str, int, int], float]:
        """Digest-proxy attention mass per committed page window.

        For each key-bearing kind (``k`` / ``c_kv``), every committed
        token row is scored ``<row, digest>`` and softmaxed across the
        layer's whole committed context; a window's mass is the sum of
        its rows' probabilities — the share of attention the current
        query direction would spend on that page.  Keyed by
        ``(kind, layer, start)``; V pages inherit their K twin's mass
        (values move under the weights keys produce)."""
        kinds = self._kinds()
        paged = (self.pos // self.page_tokens) * self.page_tokens
        masses: Dict[Tuple[str, int, int], float] = {}
        if paged <= 0:
            return masses
        for kind in ("k", "c_kv"):
            if kind not in kinds:
                continue
            buf = np.asarray(self._kv(kind))
            digest = self._query_digest(kind)
            n_layers = buf.shape[0]
            for layer in range(n_layers):
                rows = (buf[layer][:, :paged].astype(np.float32)
                        .reshape(self.batch, paged, -1))
                dots = rows @ digest                      # (B, paged)
                p = np.exp(dots - dots.max())
                p /= p.sum()
                for start in range(0, paged, self.page_tokens):
                    masses[(kind, layer, start)] = float(
                        p[:, start : start + self.page_tokens].sum())
        return masses

    def _apply_attention_importance(self, batch_pages: List[tuple]) -> List[tuple]:
        """Satellite of the PNM PR: make ``pool.update_importance`` have
        a real caller.  Accumulates this boundary's attention masses into
        the per-key running totals, re-ranks the pool's live pages, and
        rewrites the fresh commit batch so new pages are admitted at
        their measured mass instead of recency."""
        masses = self._attention_masses()
        if not masses:
            return batch_pages

        def _mass(kind: str, layer: int, start: int) -> Optional[float]:
            src = "k" if kind in ("k", "v") else kind
            return masses.get((src, layer, start))

        for p in self.pool.iter_pages():
            m = _mass(p.kind, p.layer, p.start)
            if m is not None:
                self._imp_acc[p.key] = self._imp_acc.get(p.key, 0.0) + m
        known = {p.key for p in self.pool.iter_pages()}
        scores = {k: v for k, v in self._imp_acc.items() if k in known}
        if scores:
            self.pool.update_importance(scores)
        out = []
        for entry in batch_pages:
            layer, kind, start, u16, imp = entry[:5]
            share = entry[5] if len(entry) > 5 else None
            if share is not None and self.pool.prefix_index is not None:
                key = shared_page_key(share, layer, kind)
            else:
                key = f"{self.pool.key_prefix}L{layer}.{kind}.{start}"
            m = _mass(kind, layer, start)
            if m is not None:
                self._imp_acc[key] = self._imp_acc.get(key, 0.0) + m
                imp = self._imp_acc[key]
            out.append((layer, kind, start, u16, imp, share))
        return out

    def _apply_readback(self, pages: Sequence[_Page],
                        data: Sequence[np.ndarray]):
        """Replace spilled pages' jnp-cache content with the tier-served
        values at their policy precision, so generation quality actually
        reflects the device pipeline (and DRAM reads are tallied).  All
        spilled pages of one boundary reach the device as a single request
        batch (vectorized plane decode on the device side)."""
        import ml_dtypes

        with obs.span("commit.apply", self.tag):
            layers: Dict[str, np.ndarray] = {}
            for page, u16 in zip(pages, data):
                buf = layers.get(page.kind)
                if buf is None:
                    buf = np.asarray(self._kv(page.kind))
                    obs.count("commit.d2h_bytes", buf.nbytes)
                target = buf[page.layer][:, page.start : page.start + self.page_tokens]
                vals = u16.view(ml_dtypes.bfloat16).reshape(target.shape)
                buf = buf.copy()
                obs.count("commit.copy_bytes", buf.nbytes)
                buf[page.layer][:, page.start : page.start + self.page_tokens] = vals
                layers[page.kind] = buf
            if layers:
                self._put_kv(layers)

    # -- API ---------------------------------------------------------------------
    def prefill(self, tokens: np.ndarray) -> np.ndarray:
        """tokens: (batch, prompt_len) → last-token logits."""
        B, S = tokens.shape
        assert B == self.batch
        batch = {
            "tokens": jnp.asarray(tokens),
            "cache_pos": jnp.int32(self.pos),
        }
        with obs.span("model.prefill", self.tag):
            if self.slots is None:
                logits, self.cache = self._prefill(self.params, batch,
                                                   self.cache)
            else:
                logits, cache = self._prefill(self.params, batch,
                                              self.slots.view(self.row))
                self.slots.write(self.row, cache["layers"])
        obs.count("model.prefill_chunked", int(query_chunk(S) > 0))
        old = self.pos
        self.pos += S
        if self.slots is not None:
            self.slots.park(self.row, self.pos)
        if old == 0 and self.pool.prefix_index is not None:
            self._share_hashes = prefix_chain_hashes(tokens, self.page_tokens)
            self._prompt_len = S
        self._commit_pages(old, self.pos)
        with obs.span("model.sync", self.tag):
            return np.asarray(logits[:, -1])

    def stage(self, tokens: np.ndarray):
        """Queue ``tokens`` (``(batch, 1)``) for the shared step of the
        next decode round; nothing for an engine with its own cache."""
        if self.slots is not None:
            self.slots.stage(self.row, tokens, self.pos)

    def decode(self, tokens: np.ndarray) -> np.ndarray:
        """tokens: (batch, 1) current token → next-token logits.  On slot
        rows the round's first call runs the step for every staged row."""
        if self.slots is not None:
            logits = self.slots.decode(self.row, tokens, self.pos)
            old = self.pos
            self.pos += 1
            self._commit_pages(old, self.pos)
            return logits
        batch = {
            "tokens": jnp.asarray(tokens.reshape(self.batch, 1)),
            "cache_pos": jnp.int32(self.pos),
        }
        with obs.span("model.decode", self.tag):
            logits, self.cache = self._decode(self.params, batch, self.cache)
        old = self.pos
        self.pos += 1
        self._commit_pages(old, self.pos)
        with obs.span("model.sync", self.tag):
            return np.asarray(logits[:, -1])

    def generate(self, prompt: np.ndarray, n_tokens: int,
                 greedy: bool = True, seed: int = 0) -> np.ndarray:
        rng = np.random.default_rng(seed)
        logits = self.prefill(prompt)
        out = []
        for _ in range(n_tokens):
            nxt = _sample_next(logits, rng, greedy)
            out.append(nxt)
            logits = self.decode(nxt.reshape(-1, 1))
        return np.stack(out, axis=1)

    # -- KV readback through the tier (quality measurement path) ---------------
    def kv_through_tier(self, layer: int, kind: str = "k") -> np.ndarray:
        """Token-major KV for (layer, kind) as the host would see it after a
        round-trip through the tier at the current policy."""
        self.flush_io()
        return self.pool.read_layer(layer, kind)

    def layer_traffic(self):
        """Per-layer tier traffic, attributed from the pool's receipts."""
        self.flush_io()
        return self.pool.traffic_by_layer()

    def stats(self) -> ServeStats:
        self.flush_io()
        d = self.pool.stats()
        return ServeStats(
            tokens_generated=self.pos,
            hbm_page_bytes=self.pool.hbm_bytes,
            tier_dram_read=d.dram_bytes_read,
            tier_dram_stored=d.dram_bytes_stored,
            tier_link_out=d.link_bytes_out,
            spilled_pages=self.pool.spilled_pages,
            kv_logical_bytes=d.raw_bytes_stored + self.pool.hbm_bytes,
            tier_io_service_s=self.pool.io_service_s,
            tier_io_queue_delay_s=self.pool.io_queue_delay_s,
            tier_device_compute_s=d.device_compute_s,
        )

    def throughput_ceiling(self, sys: SystemSpec = SystemSpec()) -> float:
        """tok/s ceiling implied by current per-step tier traffic."""
        d = self.pool.stats()
        steps = max(self.pos, 1)
        ddr_per_step = d.dram_bytes_read / steps
        link_per_step = d.link_bytes_out / steps
        t = max(ddr_per_step / sys.cxl_ddr_bw,
                link_per_step / sys.cxl_link_bw, 1e-12)
        return min(1.0 / t, sys.cap_tok_s)

    def retire(self) -> int:
        """Finish this sequence: drain in-flight readback, then free every
        page — HBM residents and the tier's per-stream key namespace — so
        the capacity serves the next admitted request (continuous
        batching's leave-at-commit-boundary).  Returns the number of tier
        keys freed.  The engine must not decode after retirement."""
        self.flush_io()
        if self.slots is not None:
            self.slots.release(self.row)
        return self.pool.release()


class MultiStreamEngine:
    """N independent sequences sharing one tier device queue.

    Each stream is a full :class:`ServeEngine` (own jnp cache, own page
    pool, own HBM budget) but all pools write/read through a single
    :class:`TierStore`, namespaced by a per-stream key prefix.  Decode
    proceeds round-robin one token at a time: each round's readback
    tickets accumulate in the shared in-flight window, and the first
    stream whose commit boundary finds its tickets still queued drains
    the whole window — the device coalesces reads *across* streams into
    one vectorized slab decode, and receipts price the queueing on the
    shared DDR + link pipes.  The async queue preserves per-key program
    order, so stream results are bit-identical to running each stream
    alone.
    """

    def __init__(
        self,
        cfg: ArchConfig,
        params,
        n_streams: int,
        *,
        device_kind: Union[str, TierStore] = "trace",
        async_io: bool = True,
        sanitize: Optional[bool] = None,
        shards: Optional[int] = None,
        placement: Optional[str] = None,
        **engine_kw,
    ):
        # shards=None defers to the TRACE_SHARDS env var (make_device);
        # >1 stripes every stream's pages across a device fleet.
        self.device = (make_device(device_kind, shards=shards,
                                   placement=placement, sanitize=sanitize)
                       if isinstance(device_kind, str) else device_kind)
        self.streams = [
            ServeEngine(cfg, params, device_kind=self.device,
                        key_prefix=f"s{i}.", async_io=async_io, **engine_kw)
            for i in range(n_streams)
        ]

    def generate(self, prompts: Sequence[np.ndarray], n_tokens: int,
                 greedy: bool = True, seed: int = 0) -> List[np.ndarray]:
        """Round-robin generation; ``prompts[i]`` is stream *i*'s (batch,
        prompt_len) tokens.  Returns per-stream (batch, n_tokens) arrays."""
        assert len(prompts) == len(self.streams)
        rngs = [np.random.default_rng(seed + i) for i in range(len(prompts))]
        logits = [eng.prefill(p) for eng, p in zip(self.streams, prompts)]
        outs: List[List[np.ndarray]] = [[] for _ in self.streams]
        for _ in range(n_tokens):
            for i, eng in enumerate(self.streams):
                nxt = _sample_next(logits[i], rngs[i], greedy)
                outs[i].append(nxt)
                logits[i] = eng.decode(nxt.reshape(-1, 1))
        return [np.stack(o, axis=1) for o in outs]

    def flush_io(self):
        for eng in self.streams:
            eng.flush_io()

    def stats(self) -> List[ServeStats]:
        """Per-stream stats (shared-device aggregates are identical)."""
        return [eng.stats() for eng in self.streams]

    def device_stats(self):
        self.flush_io()
        return self.device.stats

    def throughput_ceiling(self, sys: SystemSpec = SystemSpec()) -> float:
        """Aggregate tok/s ceiling across streams on the shared device."""
        self.flush_io()
        d = self.device.stats
        steps = max(sum(eng.pos for eng in self.streams), 1)
        t = max(d.dram_bytes_read / steps / sys.cxl_ddr_bw,
                d.link_bytes_out / steps / sys.cxl_link_bw, 1e-12)
        return min(1.0 / t, sys.cap_tok_s)


# ---------------------------------------------------------------------------
# Continuous batching — request arrival/departure over one shared device
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ServeRequest:
    """One serving request for the continuous-batching scheduler.

    ``arrival`` is measured in scheduler decode rounds (the clock a
    :func:`repro.core.synth.request_trace` produces); ``prompt`` is
    ``(batch, prompt_len)`` int32 token ids; ``seed`` feeds the same
    per-request sampling rng a solo :meth:`ServeEngine.generate` call
    would use, which is what makes the differential guarantee testable.
    """

    req_id: int
    arrival: float
    prompt: np.ndarray
    max_new_tokens: int
    greedy: bool = True
    seed: int = 0


@dataclasses.dataclass
class RequestRecord:
    """Lifecycle record the scheduler keeps per request.

    Steps are scheduler clock ticks; ``t_*_s`` stamps are modeled seconds
    (compute quantum ⊔ tier-I/O time per tick).  ``tokens`` is filled at
    retirement with the ``(batch, max_new_tokens)`` generation.
    """

    req_id: int
    arrival: float
    kv_projected_bytes: int = 0
    kv_novel_bytes: int = -1    # admission charge after the shared-prefix
                                # discount (-1 until computed at admission)
    admit_step: int = -1
    first_token_step: int = -1
    finish_step: int = -1
    t_arrive_s: float = -1.0
    t_admit_s: float = -1.0
    t_first_token_s: float = -1.0
    t_finish_s: float = -1.0
    prefill_tokens: int = 0
    tokens: Optional[np.ndarray] = None

    @property
    def finished(self) -> bool:
        return self.tokens is not None

    @property
    def kv_charged_bytes(self) -> int:
        """What admission actually charged against ``kv_capacity_bytes``:
        the novel-KV projection when prefix sharing discounted already-
        resident prompt windows, else the full projection.  Retirement
        returns exactly this amount."""
        return (self.kv_novel_bytes if self.kv_novel_bytes >= 0
                else self.kv_projected_bytes)

    @property
    def queue_delay_s(self) -> float:
        """Arrival → admission wait (slot or KV-capacity contention)."""
        return self.t_admit_s - self.t_arrive_s

    @property
    def latency_s(self) -> float:
        """Arrival → last generated token, in modeled seconds."""
        return self.t_finish_s - self.t_arrive_s

    @property
    def ttft_s(self) -> float:
        """Time to first token: arrival → the tick that produced the
        first generated token (queue wait + prefill + first round)."""
        return self.t_first_token_s - self.t_arrive_s

    @property
    def tpot_s(self) -> float:
        """Time per output token: mean inter-token gap *after* the
        first token.  NaN for single-token requests — there is no
        inter-token gap to measure (the explicit empty-denominator
        value, tested)."""
        if self.tokens is None or self.tokens.shape[1] <= 1:
            return float("nan")
        return ((self.t_finish_s - self.t_first_token_s)
                / (self.tokens.shape[1] - 1))


@functools.lru_cache(maxsize=32)
def _kv_bytes_per_token_b1(cfg: ArchConfig) -> int:
    """Batch-1 paged-KV bytes one committed token contributes, from the
    cache spec (``jax.eval_shape`` — no allocation).  Bounded: one entry
    per architecture, never per batch size."""
    spec = jax.eval_shape(lambda: init_cache(cfg, 1, 8))
    layers = spec.get("layers", {})
    total = 0
    for kind in ("k", "v", "c_kv"):
        if kind in layers:
            shape = layers[kind].shape          # (L, 1, S, ...channels)
            per_token = int(np.prod(shape[3:])) if len(shape) > 3 else 1
            total += int(shape[0]) * per_token * 2
    return total


def _kv_bytes_per_token(cfg: ArchConfig, batch: int) -> int:
    """Paged-KV bytes one committed token contributes at ``batch``.

    The per-token increment is exactly linear in batch (every KV leaf is
    ``(L, B, S, channels…)``), so only the batch-1 slope is traced and
    cached — a long-running server that sees many batch sizes re-traces
    nothing and the cache stays bounded by the number of architectures.
    """
    return _kv_bytes_per_token_b1(cfg) * batch


def projected_kv_bytes(cfg: ArchConfig, batch: int, total_tokens: int,
                       page_tokens: int,
                       per_token: Optional[int] = None) -> int:
    """Logical BF16 bytes of paged KV a ``total_tokens`` sequence commits.

    Admission control needs the footprint BEFORE running the model, so
    this derives it from the cache spec: every KV leaf
    (``k``/``v``/``c_kv``) contributes ``n_layers * batch *
    paged_tokens * per_token_channels * 2`` bytes, where
    ``paged_tokens`` counts only completed page windows (partial tails
    never reach the pool).  SSM/hybrid caches have no paged KV and
    project to zero.  ``per_token`` short-circuits the cache-spec lookup
    with an already-known per-token increment (the scheduler's cached
    slope) — one formula either way, so the two paths cannot drift.
    """
    paged = (total_tokens // page_tokens) * page_tokens
    if paged <= 0:
        return 0
    if per_token is None:
        per_token = _kv_bytes_per_token(cfg, batch)
    return paged * per_token


class _ActiveSeq:
    """One admitted request: its engine, sampling rng and progress."""

    __slots__ = ("req", "record", "engine", "rng", "logits", "out", "done")

    def __init__(self, req: ServeRequest, record: RequestRecord,
                 engine: ServeEngine, rng: np.random.Generator,
                 logits: np.ndarray):
        self.req = req
        self.record = record
        self.engine = engine
        self.rng = rng
        self.logits = logits
        self.out: List[np.ndarray] = []
        self.done = False


@dataclasses.dataclass
class SchedulerReport:
    """End-of-run roll-up: per-request records + modeled aggregates.

    ``peak_active`` is the largest concurrently admitted batch the run
    reached — the quantity the capacity-model sweep compares across
    `logical` and `physical` admission.  ``reclaimed_bytes`` totals the
    physical bytes precision-elastic reclamation freed (0 with the
    ladder disabled).  Every percentile/mean property returns an
    explicit value on an empty denominator (NaN) instead of raising —
    zero finished requests is a legal report state, tested.
    """

    records: List[RequestRecord]
    steps: int
    model_time_s: float
    decode_tokens: int
    prefill_tokens: int
    peak_active: int = 0
    capacity_model: str = "logical"
    kv_ratio_estimate: float = 1.0
    reclaimed_bytes: int = 0
    slo_ttft_s: Optional[float] = None
    slo_tpot_s: Optional[float] = None
    # Fleet view: how many tier devices served the run, and how skewed
    # the per-device traffic ended up (max/mean moved bytes; 1.0 for a
    # single device or a perfectly balanced fleet).
    n_devices: int = 1
    fleet_skew: float = 1.0

    @property
    def slo_attainment(self) -> float:
        """Fraction of finished requests meeting BOTH configured SLOs
        (TTFT and TPOT, modeled seconds).  An unset target is vacuously
        met; a single-token request has no inter-token gap (``tpot_s``
        is NaN), so only its TTFT can miss — NaN never counts as a
        violation.  NaN when no SLO is configured or nothing finished.
        """
        if self.slo_ttft_s is None and self.slo_tpot_s is None:
            return float("nan")
        done = [r for r in self.records if r.finished]
        if not done:
            return float("nan")
        ok = 0
        for r in done:
            if (self.slo_ttft_s is not None
                    and not r.ttft_s <= self.slo_ttft_s):
                continue
            if (self.slo_tpot_s is not None and np.isfinite(r.tpot_s)
                    and not r.tpot_s <= self.slo_tpot_s):
                continue
            ok += 1
        return ok / len(done)

    @property
    def tok_s(self) -> float:
        """Decode throughput over the modeled run (generated tokens only)."""
        return self.decode_tokens / max(self.model_time_s, 1e-12)

    def latency_percentile(self, q: float) -> float:
        lats = [r.latency_s for r in self.records if r.finished]
        return float(np.percentile(lats, q)) if lats else float("nan")

    @property
    def p50_latency_s(self) -> float:
        return self.latency_percentile(50)

    @property
    def p99_latency_s(self) -> float:
        return self.latency_percentile(99)

    @property
    def mean_queue_delay_s(self) -> float:
        qs = [r.queue_delay_s for r in self.records if r.finished]
        return float(np.mean(qs)) if qs else float("nan")

    def ttft_percentile(self, q: float) -> float:
        ts = [r.ttft_s for r in self.records if r.finished]
        return float(np.percentile(ts, q)) if ts else float("nan")

    @property
    def p50_ttft_s(self) -> float:
        return self.ttft_percentile(50)

    @property
    def p99_ttft_s(self) -> float:
        return self.ttft_percentile(99)

    @property
    def mean_tpot_s(self) -> float:
        """Mean time-per-output-token across finished multi-token
        requests (single-token requests have no inter-token gap and are
        excluded; NaN when none qualify)."""
        ts = [r.tpot_s for r in self.records
              if r.finished and np.isfinite(r.tpot_s)]
        return float(np.mean(ts)) if ts else float("nan")


class ServeScheduler:
    """Continuous-batching request scheduler over one shared tier device.

    Requests arrive on a synthetic trace (``arrival`` in decode rounds —
    see :func:`repro.core.synth.request_trace`), wait FIFO for a batch
    slot, run prefill-then-decode as a full :class:`ServeEngine` under a
    per-request key namespace (``r{id}.``), and retire at the commit
    boundary that produced their last token — :meth:`ServeEngine.retire`
    frees HBM pages and deletes the request's tier namespace
    (:meth:`TierStore.delete_prefix`), so the freed slot and KV capacity
    admit the next queued request.  All active engines share ONE device
    queue: their spill readback tickets coalesce into cross-request slab
    decodes and the busy clock prices cross-request pipe contention, just
    like :class:`MultiStreamEngine`.

    Admission is KV-capacity-aware: with ``kv_capacity_bytes`` set, a
    request joins only when the committed KV projection of every active
    request plus its own fits; the queue does NOT bypass a blocked
    head-of-line request (strict FIFO).  A request too large for the
    whole capacity is still admitted when the batch is empty, so the
    queue cannot deadlock.  The per-request projection is the cached
    per-token increment (one ``jax.eval_shape`` trace per (cfg, batch))
    times the request's completed page windows — admission checks are
    pure arithmetic.

    Two capacity models (``capacity_model``):

    * `logical` — the projection is compared against capacity as raw
      BF16 bytes (the conservative pre-ledger behavior, and the only
      sound model for a device whose stored footprint equals its
      logical footprint).
    * `physical` — the projection is divided by a feedback estimate of
      the device's compression ratio before the comparison.  The
      estimator seeds at 1.0 (no stored data: admit exactly like
      `logical`), reads the device-observed running ratio from the
      residency ledger (``TierStore.resident_bytes`` /
      ``compression_ratio``) and corrects itself against ledger deltas
      at every commit boundary.  A trace device storing KV at >2x
      therefore admits a strictly larger concurrent batch than a word
      device at the same ``kv_capacity_bytes`` — the paper's
      compression ratio acting as the serving control signal rather
      than a reporting statistic.

    Precision-elastic reclamation: with a ``degrade_ladder`` configured
    (and the `physical` model), a blocked head-of-line request triggers
    :meth:`KVPagePool.reclaim` across the active requests' pools before
    admission stalls — cold stored pages shed mantissa planes in place,
    the ledger shrinks, the ratio estimate rises, and the admission
    check is retried.  With the ladder disabled (the default) stored
    bytes are never touched and per-request tokens stay bit-identical
    to solo runs.

    Shared-prefix KV reuse (``prefix_share=True``): every engine's pool
    is wired to one :class:`PrefixShareIndex`, so identical completed
    prompt-prefix pages are stored once under the content-addressed
    ``shared.`` namespace (refcounted in the residency ledger, freed when
    the last referer retires) and the spill write is elided for every
    request after the first — and admission charges each request only its
    *novel* projection (frozen into ``RequestRecord.kv_novel_bytes`` so
    retirement refunds exactly what was charged).  At high prefix overlap this
    multiplies the admissible concurrent batch and cuts TTFT twice over:
    less queue wait and fewer spill bytes per tick.  Sharing preserves
    the differential guarantee — a reused page stores exactly the bytes
    the request's own write would have stored.

    The differential guarantee extends to dynamic membership: per-key
    program order on the shared queue means each request's decoded tokens
    are bit-identical to running it solo through
    ``ServeEngine.generate(prompt, n, greedy, seed)`` at the same
    ``max_seq`` — joins, leaves and capacity stalls change receipts'
    latency (queue delay), never data.

    Batched decode: where the model's cache is the plain GQA ``k``/``v``
    layout (:func:`slot_layout`), the scheduler owns one
    :class:`SlotCache` of ``max_batch * batch`` rows, each admitted
    request's engine holds its slot's rows, and a tick decodes every
    active request in one jitted step, reading the weights once.  Other
    layouts keep one cache and one step per request.

    Modeled time: every scheduler tick costs
    ``max(1/cap_tok_s, tier I/O time of the tick)`` seconds — one batched
    decode round at the compute ceiling, or the tick's DRAM/link transfer
    time when the tier is the bottleneck (the regime Figs. 12-14 study).
    """

    def __init__(
        self,
        cfg: ArchConfig,
        params,
        *,
        max_batch: int = 4,
        device_kind: Union[str, TierStore] = "trace",
        policy=None,
        batch: int = 1,
        page_tokens: int = 16,
        hbm_kv_budget: int = 1 << 12,
        max_seq: Optional[int] = None,
        kv_capacity_bytes: Optional[int] = None,
        capacity_model: str = "logical",
        degrade_ladder: Optional[Sequence] = None,
        async_io: bool = True,
        sys: SystemSpec = SystemSpec(),
        sanitize: Optional[bool] = None,
        prefix_share: bool = False,
        slo_ttft_s: Optional[float] = None,
        slo_tpot_s: Optional[float] = None,
        shards: Optional[int] = None,
        placement: Optional[str] = None,
        pnm_topk: Optional[int] = None,
        importance: str = "recency",
    ):
        from .paging import PAPER_POLICY as _paper

        if capacity_model not in ("logical", "physical"):
            raise ValueError(f"unknown capacity model {capacity_model!r}")
        if degrade_ladder and capacity_model != "physical":
            # Reclamation frees *stored* bytes; logical admission compares
            # raw projections that never shrink, so a ladder could only
            # destroy precision without ever unblocking anything.  Refuse
            # loudly rather than silently ignoring the flag.
            raise ValueError(
                "degrade_ladder requires capacity_model='physical'"
            )
        self.cfg = cfg
        self.params = params
        # The fleet routing layer: shards > 1 builds a ShardedTierStore,
        # and because every engine replica this scheduler starts keys its
        # pages under its own `r{id}.` namespace, the placement policy
        # spreads the replicas' traffic across the device fleet (hash-
        # stripe: per-page; namespace: whole replicas pinned per shard).
        # shards=None defers to the TRACE_SHARDS env var (make_device).
        self.device = (make_device(device_kind, shards=shards,
                                   placement=placement, sanitize=sanitize)
                       if isinstance(device_kind, str) else device_kind)
        self.max_batch = max_batch
        self.policy = _paper if policy is None else policy
        self.batch = batch
        self.page_tokens = page_tokens
        self.hbm_kv_budget = hbm_kv_budget
        self.kv_capacity_bytes = kv_capacity_bytes
        self.capacity_model = capacity_model
        self.degrade_ladder = tuple(degrade_ladder or ())
        self.async_io = async_io
        self.sys = sys
        # SLO targets (modeled seconds) carried into every report; the
        # scheduler itself never gates on them — attainment is a
        # reporting statistic, not an admission signal
        self.slo_ttft_s = slo_ttft_s
        self.slo_tpot_s = slo_tpot_s
        # PNM read mode + importance signal, threaded into every engine
        # this scheduler starts.
        self.pnm_topk = pnm_topk
        self.importance = importance
        # Shared-prefix KV reuse: one content-addressed index across every
        # engine this scheduler starts.  Identical prompt-prefix pages are
        # stored once (refcounted), and admission charges each request only
        # its NOVEL projection (see _novel_bytes).
        self.prefix_index = (PrefixShareIndex(self.device)
                             if prefix_share else None)
        self._max_seq = max_seq
        self.pending: List[ServeRequest] = []
        self.active: List[Optional[_ActiveSeq]] = [None] * max_batch
        self.records: Dict[int, RequestRecord] = {}
        self.clock = 0                  # scheduler ticks (decode rounds)
        self.model_time_s = 0.0
        self.kv_committed_bytes = 0     # projections of active requests
        self.peak_active = 0            # largest admitted batch reached
        self.reclaimed_bytes = 0        # ladder-freed physical bytes
        # Ratio-aware admission feedback: seeds neutral (admit like the
        # logical model), tracks the device-observed running compression
        # ratio from the residency ledger, corrected at every commit
        # boundary (see _update_ratio).
        self.kv_ratio_estimate = 1.0
        self._ratio_seeded = False
        self._ledger_mark = (0, 0)      # (raw, physical) at last correction
        self._kv_per_token: Optional[int] = None   # cached projection slope
        self._first_this_tick: List[RequestRecord] = []
        self._slots: Optional[SlotCache] = None
        self._next_id = 0
        self._io_mark = self._io_snapshot()

    @property
    def max_seq(self) -> Optional[int]:
        """Largest sequence budget any submitted request has needed so far
        (grown by :meth:`submit`; ``None`` until the first request)."""
        return self._max_seq

    # -- request intake ------------------------------------------------------
    def submit(self, requests: Sequence[Union[ServeRequest, dict]]):
        """Add requests (``ServeRequest`` or ``request_trace`` dicts) to
        the arrival queue.  Ids are assigned to dict entries; the queue is
        kept sorted by (arrival, id)."""
        for r in requests:
            if isinstance(r, dict):
                r = ServeRequest(req_id=self._next_id, **r)
            self._next_id = max(self._next_id, r.req_id + 1)
            if r.max_new_tokens < 1:
                raise ValueError("requests must generate at least one token")
            if r.prompt.shape[0] != self.batch:
                raise ValueError(
                    f"prompt batch {r.prompt.shape[0]} != scheduler batch "
                    f"{self.batch}"
                )
            if r.req_id in self.records:
                raise ValueError(f"duplicate req_id {r.req_id}")
            total = r.prompt.shape[-1] + r.max_new_tokens
            need = total + self.page_tokens
            if self._max_seq is None or self._max_seq < need:
                self._max_seq = max(self._max_seq or 0, need)
            # The projection is the cached per-token increment times the
            # request's completed page windows — the eval_shape trace
            # runs once per scheduler, not once per admission check.
            if self._kv_per_token is None:
                self._kv_per_token = _kv_bytes_per_token(self.cfg, self.batch)
            self.records[r.req_id] = RequestRecord(
                req_id=r.req_id, arrival=r.arrival,
                kv_projected_bytes=projected_kv_bytes(
                    self.cfg, self.batch, total, self.page_tokens,
                    per_token=self._kv_per_token),
            )
            self.pending.append(r)
        self.pending.sort(key=lambda r: (r.arrival, r.req_id))

    # -- one scheduler tick --------------------------------------------------
    def step(self) -> bool:
        """One commit-boundary round: admit arrivals into free slots, run
        one decode step for every active sequence, advance the modeled
        clock, retire finished sequences.  Returns True while work (queued
        or active) remains; an idle tick (nothing arrived yet) still
        advances both clocks.

        Finished sequences' engine teardown (readback drain + namespace
        delete) runs BEFORE the tick's time advance, so retirement I/O is
        priced into the same tick — including the run's final tick, which
        has no later tick to absorb it."""
        with obs.tick(self.clock):
            self._admit()
            self.peak_active = max(self.peak_active, self.n_active)
            self._decode_round()
            for seq in self.active:
                if seq is not None and seq.done:
                    seq.engine.retire()
            self._advance_time()
            # First-token stamps land after the tick's time advance: TTFT
            # includes the round that actually produced the token.
            for rec in self._first_this_tick:
                rec.first_token_step = self.clock
                rec.t_first_token_s = self.model_time_s
            self._first_this_tick.clear()
            with obs.span("sched.ratio"):
                self._update_ratio()
            self._retire()
            self.clock += 1
        return bool(self.pending or any(s is not None for s in self.active))

    def run(self, requests: Optional[Sequence] = None,
            max_steps: int = 1_000_000) -> SchedulerReport:
        """Drive :meth:`step` until every submitted request has retired."""
        if requests:
            self.submit(requests)
        while self.step():
            max_steps -= 1
            if max_steps <= 0:
                raise RuntimeError("scheduler failed to drain")
        return self.report()

    def report(self) -> SchedulerReport:
        done = [self.records[k] for k in sorted(self.records)
                if self.records[k].finished]
        return SchedulerReport(
            records=done,
            steps=self.clock,
            model_time_s=self.model_time_s,
            decode_tokens=sum(r.tokens.size for r in done),
            prefill_tokens=sum(r.prefill_tokens for r in done),
            peak_active=self.peak_active,
            capacity_model=self.capacity_model,
            kv_ratio_estimate=self.kv_ratio_estimate,
            reclaimed_bytes=self.reclaimed_bytes,
            slo_ttft_s=self.slo_ttft_s,
            slo_tpot_s=self.slo_tpot_s,
            n_devices=len(self._device_stat_list()),
            fleet_skew=getattr(self.device, "fleet_skew", lambda: 1.0)(),
        )

    # -- internals -----------------------------------------------------------
    def _device_stat_list(self):
        """Per-device stats: each entry is one device's own pipes.  A
        single TierStore is a one-entry fleet; a sharded device exposes
        ``per_device_stats`` and the tick's I/O time becomes the slowest
        shard's (the straggler), not the fleet total over one pipe."""
        per = getattr(self.device, "per_device_stats", None)
        return per() if per is not None else [self.device.stats]

    def _io_snapshot(self):
        return [(d.dram_bytes_read + d.dram_bytes_written,
                 d.link_bytes_in + d.link_bytes_out)
                for d in self._device_stat_list()]

    def _projected_physical(self, logical_bytes: int) -> int:
        """Map a logical-KV projection to the bytes the device is
        expected to store for it under the current ratio estimate."""
        if self.capacity_model == "logical":
            return logical_bytes
        return int(np.ceil(logical_bytes
                           / max(self.kv_ratio_estimate, 1e-6)))

    def _novel_bytes(self, req: ServeRequest, rec: RequestRecord) -> int:
        """The admission charge for one request: its full KV projection
        minus the leading prompt windows whose shared pages are already
        stored on the device (``PrefixShareIndex.resident_chain``).

        Computed at each admission attempt — the index changes as other
        requests prefill and retire — and frozen into the record at
        admission so retirement returns exactly what was charged.  It is
        a projection like everything else admission uses: a referenced
        shared page stays alive while this request runs (the pool
        acquires it at spill), but a page counted here could free between
        this check and this request's own spill, in which case the pool
        simply writes it again — same estimate-then-correct contract as
        the ratio feedback.
        """
        if self.prefix_index is None:
            return rec.kv_projected_bytes
        hashes = prefix_chain_hashes(req.prompt, self.page_tokens)
        hit_windows = self.prefix_index.resident_chain(hashes)
        shared = hit_windows * self.page_tokens * (self._kv_per_token or 0)
        return max(rec.kv_projected_bytes - shared, 0)

    def _kv_fits(self, rec: RequestRecord) -> bool:
        if self.kv_capacity_bytes is None:
            return True
        if not any(s is not None for s in self.active):
            return True                  # empty-batch escape (no deadlock)
        need = self.kv_committed_bytes + rec.kv_charged_bytes
        return self._projected_physical(need) <= self.kv_capacity_bytes

    def _update_ratio(self):
        """Correct the admission ratio estimate against the residency
        ledger — called at every commit boundary (scheduler tick).

        Prefers the delta since the last correction (fresh commits are
        the best predictor of the next request's storage behavior);
        falls back to the absolute stored ratio when the tick freed
        bytes (retirement, reclamation) or committed nothing."""
        raw = self.device.stats.raw_bytes_stored
        phys = self.device.resident_bytes()
        d_raw = raw - self._ledger_mark[0]
        d_phys = phys - self._ledger_mark[1]
        self._ledger_mark = (raw, phys)
        if d_raw > 0 and d_phys > 0:
            obs = d_raw / d_phys
        elif raw > 0 and phys > 0:
            obs = raw / phys
        else:
            return                       # device empty: keep the estimate
        if not self._ratio_seeded:
            # first stored bytes: adopt the observed ratio outright (the
            # neutral 1.0 was a placeholder, not a measurement)
            self.kv_ratio_estimate = obs
            self._ratio_seeded = True
        else:
            self.kv_ratio_estimate += 0.5 * (obs - self.kv_ratio_estimate)

    def _reclaim_for(self, rec: RequestRecord) -> bool:
        """Blocked-admission pressure valve: shed cold stored planes
        across the active requests' pools until the head-of-line
        request's projection fits, then re-check.  Returns True when the
        reclamation unblocked admission.

        The deficit is denominated in *projected* physical bytes while
        reclaim frees *stored* bytes, so one pass is not guaranteed to
        unblock — the fit re-check only moves through the corrected
        ratio estimate.  Sustained pressure therefore keeps degrading
        cold pages, bounded by ladder exhaustion (``reclaim`` returns 0
        once every cold page sits at the last rung, and the admission
        stalls exactly like the ladderless scheduler).  That
        precision-for-capacity trade is the documented contract of
        enabling a ladder."""
        if not self.degrade_ladder or self.capacity_model != "physical":
            return False
        need = self.kv_committed_bytes + rec.kv_charged_bytes
        deficit = self._projected_physical(need) - self.kv_capacity_bytes
        freed = 0
        for seq in self.active:
            if seq is None or freed >= deficit:
                continue
            freed += seq.engine.pool.reclaim(deficit - freed,
                                             self.degrade_ladder)
        if freed == 0:
            return False
        self.reclaimed_bytes += freed
        self._update_ratio()             # the ledger just shrank
        return self._kv_fits(rec)

    def _admit(self):
        with obs.span("sched.admit"):
            # Stamp every request the trace has delivered by now: queueing
            # delay starts at arrival, not at admission.
            for r in self.pending:
                if r.arrival > self.clock:
                    break
                rec = self.records[r.req_id]
                if rec.t_arrive_s < 0:
                    rec.t_arrive_s = self.model_time_s
            free = [i for i, s in enumerate(self.active) if s is None]
            while free and self.pending and self.pending[0].arrival <= self.clock:
                req = self.pending[0]
                rec = self.records[req.req_id]
                rec.kv_novel_bytes = self._novel_bytes(req, rec)
                if not self._kv_fits(rec) and not self._reclaim_for(rec):
                    break                    # strict FIFO: wait for retirements
                self.pending.pop(0)
                self.kv_committed_bytes += rec.kv_charged_bytes
                slot = free.pop(0)
                self.active[slot] = self._start(req, rec, slot)

    def _slot_cache(self) -> Optional[SlotCache]:
        """The shared slot cache, made at the first admission and grown
        with ``max_seq``; ``None`` for a layout it cannot batch."""
        if not slot_layout(self.cfg):
            return None
        if self._slots is None:
            self._slots = SlotCache(self.cfg, self.params,
                                    self.max_batch * self.batch,
                                    self._max_seq, self.batch)
        self._slots.grow(self._max_seq)
        return self._slots

    def _start(self, req: ServeRequest, rec: RequestRecord,
               slot: int) -> _ActiveSeq:
        with obs.span("sched.engine_init", f"r{req.req_id}"):
            eng = ServeEngine(
                self.cfg, self.params, max_seq=self._max_seq, batch=self.batch,
                page_tokens=self.page_tokens, hbm_kv_budget=self.hbm_kv_budget,
                device_kind=self.device, policy=self.policy,
                key_prefix=f"r{req.req_id}.", async_io=self.async_io,
                prefix_index=self.prefix_index,
                pnm_topk=self.pnm_topk, importance=self.importance,
                slots=self._slot_cache(), row=slot * self.batch,
            )
        rec.admit_step = self.clock
        rec.t_admit_s = self.model_time_s
        rec.prefill_tokens = int(req.prompt.size)
        logits = eng.prefill(req.prompt)
        return _ActiveSeq(req, rec, eng,
                          np.random.default_rng(req.seed), logits)

    def _decode_round(self):
        """Sample every active request's next token, then decode those
        that want another: on slot rows the first ``decode`` runs one
        step for all of them, and each call commits its own pages."""
        going = []
        for seq in self.active:
            if seq is None:
                continue
            with obs.span("sched.sample", seq.engine.tag):
                nxt = _sample_next(seq.logits, seq.rng, seq.req.greedy)
            obs.count("sched.tokens", nxt.size)
            seq.out.append(nxt)
            if len(seq.out) == 1:
                self._first_this_tick.append(seq.record)
            if len(seq.out) < seq.req.max_new_tokens:
                seq.engine.stage(nxt.reshape(-1, 1))
                going.append((seq, nxt))
            else:
                seq.done = True
        for seq, nxt in going:
            seq.logits = seq.engine.decode(nxt.reshape(-1, 1))

    def _advance_time(self):
        """One tick costs the compute ceiling or the tick's tier I/O,
        whichever dominates.  Each device moves its own tick delta over
        its OWN DDR/link pipes concurrently, so the tick's I/O time is
        the slowest device's — a balanced fleet divides the I/O wall by
        ``n`` while one hot shard drags every request with it."""
        snap = self._io_snapshot()
        io_s = 0.0
        for (dram, link), (m_dram, m_link) in zip(snap, self._io_mark):
            io_s = max(io_s,
                       (dram - m_dram) / self.sys.cxl_ddr_bw,
                       (link - m_link) / self.sys.cxl_link_bw)
        self._io_mark = snap
        self.model_time_s += max(1.0 / self.sys.cap_tok_s, io_s)

    def _retire(self):
        """Record + free finished sequences (their engines were already
        torn down in :meth:`step`, before the tick's time advance)."""
        for i, seq in enumerate(self.active):
            if seq is None or not seq.done:
                continue
            rec = seq.record
            rec.tokens = np.stack(seq.out, axis=1)
            rec.finish_step = self.clock
            rec.t_finish_s = self.model_time_s
            self.kv_committed_bytes -= rec.kv_charged_bytes
            self.active[i] = None

    # -- introspection -------------------------------------------------------
    @property
    def n_active(self) -> int:
        return sum(s is not None for s in self.active)

    def device_stats(self):
        return self.device.stats
