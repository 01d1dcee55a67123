"""Serving driver: batched generation with TRACE-tiered KV offload.

Runs a (reduced or full) model with the ServeEngine, reporting tier traffic,
KV compression ratio, and the implied tok/s ceiling for each device kind —
the end-to-end integration of the paper's two mechanisms.  Spill readback
goes through the tier's queued async front-end by default (``--sync-io``
reverts to serialized submits); ``--streams N`` serves N sequences that
share one device queue; ``--num-requests N`` switches to the
continuous-batching scheduler (Poisson/bursty arrivals, capacity-aware
admission, retirement frees tier pages).

Usage (CPU demo, reduced smoke config):
  PYTHONPATH=src python -m repro.launch.serve --arch qwen2-0.5b \
      --tokens 64 --device trace --streams 2
  PYTHONPATH=src python -m repro.launch.serve --num-requests 8 \
      --arrival-rate 0.5 --max-batch 2
On a TPU, ``--full-width`` serves the architecture at its published
widths.  Figures named ``modeled_*`` are priced by the analytic link
model, not measured.
"""

from __future__ import annotations

import argparse

import jax
import numpy as np

from ..compile_cache import enable_compile_cache
from ..configs import ARCHS, smoke_config
from ..core import make_device, synth
from ..core.precision import VIEWS
from ..models.model import init_params
from ..runtime import (
    MultiStreamEngine, PAPER_POLICY, ServeEngine, ServeScheduler,
)
from ..runtime.paging import DEFAULT_DEGRADE_LADDER, LOSSLESS_POLICY


def device_line() -> str:
    """The device JAX serves on, as it reports it."""
    devs = jax.devices()
    return (f"[serve] device: platform={devs[0].platform} "
            f"kind={devs[0].device_kind} count={len(devs)}")


def parse_degrade_ladder(spec: str):
    """CLI ladder spec → tuple of PrecisionViews.

    ``"none"``/empty disables reclamation, ``"default"`` is the paper's
    man4→man2→man0 progression, otherwise a comma-separated list of view
    names from ``repro.core.precision.VIEWS`` (e.g. ``man4,man0``).
    """
    spec = (spec or "none").strip().lower()
    if spec in ("none", ""):
        return ()
    if spec == "default":
        return DEFAULT_DEGRADE_LADDER
    try:
        return tuple(VIEWS[name.strip()] for name in spec.split(","))
    except KeyError as e:
        raise SystemExit(
            f"unknown precision view {e.args[0]!r} in --degrade-ladder "
            f"(known: {', '.join(sorted(VIEWS))})"
        )

EPILOG = """\
serving modes (and the benchmark figure each corresponds to):

  sync single-stream     --sync-io                 serialized spill readback;
                                                   the fig12 baseline every
                                                   overlap win is measured
                                                   against
  async single-stream    (default)                 readback tickets ride the
                                                   in-flight window across
                                                   the jitted decode step —
                                                   fig12's decode/fetch
                                                   overlap at long context
  multi-stream           --streams N               N sequences share ONE
                                                   device queue: cross-stream
                                                   coalesced slab decodes,
                                                   busy-clock queue delay —
                                                   fig12's async-vs-sync
                                                   multi-stream tok/s
  continuous batching    --num-requests N          request arrival/departure
                         [--arrival-rate R]        over the shared queue:
                         [--max-batch M]           FIFO + KV-capacity-aware
                         [--arrival-kind K]        admission, retire frees
                         [--kv-capacity B]         pages — fig12_14's
                         [--capacity-model M]      throughput + p50/p99
                         [--degrade-ladder L]      latency vs offered load
                         [--prefix-share]          shared-prefix KV reuse
                         [--share-prefix-len N]

  Every mode accepts --pnm-topk K: spill readback becomes a processing-
  near-memory gather — the device scores spilled pages on a reduced
  plane subset (sign + exponent + one guard mantissa plane) against the
  current query digest and ships full precision for only the top-K, so
  link bytes per boundary are O(K·page) instead of O(spilled·page).
  K >= spilled pages is bit-identical to the classic readback.
  --importance attention feeds measured attention mass into page
  ranking (residency, spill views) instead of commit recency.

  Every mode accepts --shards N (with --placement P): the tier becomes a
  ShardedTierStore fleet of N devices, each with its own LinkModel pipes
  and busy clock.  hash-stripe spreads each request's pages across the
  fleet, namespace pins whole request namespaces per shard, and
  replicate-weights copies TENSOR-kind writes to every shard with read
  fan-out to the least-busy replica.  Receipts carry the serving
  device_id; the continuous-batching report adds n_devices + fleet_skew.

  The physical capacity model admits against the device's residency
  ledger (projection / observed compression ratio) instead of logical
  BF16 bytes — trace devices admit a larger concurrent batch at the
  same --kv-capacity; a degrade ladder (e.g. "man4,man2,man0") lets a
  blocked admission reclaim stored bytes by shedding mantissa planes of
  cold pages in place before stalling — fig12_14's capacity sweep.

  --prefix-share stores identical completed prompt-prefix pages once
  under the content-addressed shared. namespace (refcounted in the
  residency ledger, copy-on-write past the divergence point) and
  charges each admission only its *novel* KV projection;
  --share-prefix-len makes the synthetic trace share its leading N
  prompt tokens (a common system prompt) so the reuse has something to
  bite on — fig12_14's prefix-share sweep.

All modes keep per-sequence outputs bit-identical to a solo run of the
same request; see docs/ARCHITECTURE.md for the dataflow.
"""


def serve(
    arch: str = "qwen2-0.5b",
    smoke: bool = True,
    device: str = "trace",
    prompt_len: int = 64,
    n_tokens: int = 32,
    batch: int = 2,
    hbm_kv_budget: int = 1 << 12,   # tiny on purpose → force KV spill to tier
    page_tokens: int = 16,
    lossless_only: bool = False,
    streams: int = 1,
    async_io: bool = True,
    seed: int = 0,
    sanitize: bool | None = None,
    shards: int | None = None,
    placement: str | None = None,
    pnm_topk: int | None = None,
    importance: str = "recency",
):
    cfg = ARCHS[arch]
    if smoke:
        cfg = smoke_config(cfg)
    params = init_params(cfg, jax.random.PRNGKey(seed))
    policy = LOSSLESS_POLICY if lossless_only else PAPER_POLICY
    kw = dict(
        max_seq=prompt_len + n_tokens + page_tokens,
        batch=batch,
        page_tokens=page_tokens,
        hbm_kv_budget=hbm_kv_budget,
        policy=policy,
        async_io=async_io,
        sanitize=sanitize,
        pnm_topk=pnm_topk,
        importance=importance,
    )
    # Build the (possibly sharded) device up front so the solo-engine
    # path honors --shards/--placement the same way MultiStreamEngine
    # does; `device` stays the kind name for reporting.
    dev = make_device(device, shards=shards, placement=placement,
                      sanitize=sanitize)
    rng = np.random.default_rng(seed)
    if streams > 1:
        eng = MultiStreamEngine(cfg, params, streams, device_kind=dev, **kw)
        prompts = [
            rng.integers(0, cfg.vocab, (batch, prompt_len)).astype(np.int32)
            for _ in range(streams)
        ]
        toks = eng.generate(prompts, n_tokens)
        per = eng.stats()
        d = eng.device_stats()
        print(f"[serve] arch={arch} device={device} streams={streams} "
              f"async_io={async_io} generated {[t.shape for t in toks]}")
        print(f"[serve] shared tier: stored {d.dram_bytes_stored} B, "
              f"DRAM read {d.dram_bytes_read} B, link out {d.link_bytes_out} B")
        io_srv = sum(s.tier_io_service_s for s in per)
        io_qd = sum(s.tier_io_queue_delay_s for s in per)
        print(f"[serve] tier I/O (modeled): modeled_serialized_ms "
              f"{io_srv * 1e3:.3f}, modeled_queue_delay_ms {io_qd * 1e3:.3f}")
        print(f"[serve] modeled_tok_s_ceiling (aggregate): "
              f"{eng.throughput_ceiling():.1f}")
        return eng, toks
    eng = ServeEngine(cfg, params, device_kind=dev, **kw)
    if pnm_topk is not None:
        print(f"[serve] PNM read mode: device-side top-{pnm_topk} gather "
              f"per KV kind per boundary (importance={importance})")
    prompt = rng.integers(0, cfg.vocab, (batch, prompt_len)).astype(np.int32)
    toks = eng.generate(prompt, n_tokens)
    s = eng.stats()
    print(f"[serve] arch={arch} device={device} async_io={async_io} "
          f"generated {toks.shape} tokens")
    print(f"[serve] spilled pages: {s.spilled_pages}, "
          f"tier stored {s.tier_dram_stored} B for {s.kv_logical_bytes} B logical "
          f"(ratio {s.kv_compression_ratio:.2f}x)")
    print(f"[serve] tier DRAM read {s.tier_dram_read} B, link out {s.tier_link_out} B")
    print(f"[serve] tier I/O (modeled): modeled_serialized_ms "
          f"{s.tier_io_service_s * 1e3:.3f}, modeled_queue_delay_ms "
          f"{s.tier_io_queue_delay_s * 1e3:.3f}")
    print(f"[serve] modeled_tok_s_ceiling (tier-bound): "
          f"{eng.throughput_ceiling():.1f}")
    return eng, toks


def serve_continuous(
    arch: str = "qwen2-0.5b",
    smoke: bool = True,
    device: str = "trace",
    num_requests: int = 8,
    arrival_rate: float = 0.5,
    arrival_kind: str = "poisson",
    max_batch: int = 2,
    prompt_len: int = 32,
    n_tokens: int = 8,
    batch: int = 1,
    hbm_kv_budget: int = 1 << 12,
    page_tokens: int = 16,
    kv_capacity_bytes: int | None = None,
    capacity_model: str = "logical",
    degrade_ladder=(),
    prefix_share: bool = False,
    share_prefix_len: int = 0,
    lossless_only: bool = False,
    async_io: bool = True,
    seed: int = 0,
    sanitize: bool | None = None,
    shards: int | None = None,
    placement: str | None = None,
    slo_ttft_s: float | None = None,
    slo_tpot_s: float | None = None,
    pnm_topk: int | None = None,
    importance: str = "recency",
):
    """Continuous-batching mode: run a synthetic arrival trace through the
    ServeScheduler and report throughput + latency percentiles."""
    cfg = ARCHS[arch]
    if smoke:
        cfg = smoke_config(cfg)
    params = init_params(cfg, jax.random.PRNGKey(seed))
    policy = LOSSLESS_POLICY if lossless_only else PAPER_POLICY
    trace = synth.request_trace(
        num_requests, cfg.vocab, rate=arrival_rate, kind=arrival_kind,
        prompt_len=prompt_len, new_tokens=n_tokens, batch=batch, seed=seed,
        share_prefix_len=share_prefix_len,
    )
    sched = ServeScheduler(
        cfg, params, max_batch=max_batch, device_kind=device, policy=policy,
        batch=batch, page_tokens=page_tokens, hbm_kv_budget=hbm_kv_budget,
        kv_capacity_bytes=kv_capacity_bytes, capacity_model=capacity_model,
        degrade_ladder=degrade_ladder, prefix_share=prefix_share,
        async_io=async_io, sanitize=sanitize,
        shards=shards, placement=placement,
        slo_ttft_s=slo_ttft_s, slo_tpot_s=slo_tpot_s,
        pnm_topk=pnm_topk, importance=importance,
    )
    rep = sched.run(trace)
    d = sched.device_stats()
    print(f"[serve] arch={arch} device={device} continuous batching: "
          f"{num_requests} requests, {arrival_kind} rate {arrival_rate}/round, "
          f"max_batch {max_batch}, capacity model {capacity_model}")
    print(f"[serve] {rep.steps} rounds, {rep.decode_tokens} decode tokens in "
          f"modeled_ms {rep.model_time_s * 1e3:.2f} → modeled_tok_s "
          f"{rep.tok_s:.1f} (peak admitted batch {rep.peak_active})")
    print(f"[serve] modeled latency: modeled_p50_ms "
          f"{rep.p50_latency_s * 1e3:.2f}, modeled_p99_ms "
          f"{rep.p99_latency_s * 1e3:.2f}, modeled_mean_queue_delay_ms "
          f"{rep.mean_queue_delay_s * 1e3:.2f}")
    print(f"[serve] modeled_ttft_p50_ms {rep.p50_ttft_s * 1e3:.2f}, "
          f"modeled_ttft_p99_ms {rep.p99_ttft_s * 1e3:.2f}; "
          f"modeled_tpot_mean_ms {rep.mean_tpot_s * 1e3:.2f}")
    if slo_ttft_s is not None or slo_tpot_s is not None:
        targets = []
        if slo_ttft_s is not None:
            targets.append(f"TTFT <= {slo_ttft_s * 1e3:g} ms")
        if slo_tpot_s is not None:
            targets.append(f"TPOT <= {slo_tpot_s * 1e3:g} ms/tok")
        print(f"[serve] modeled SLO attainment "
              f"{rep.slo_attainment * 100:.1f}% "
              f"({' and '.join(targets)}, "
              f"{len(rep.records)} finished requests)")
    if capacity_model == "physical":
        print(f"[serve] admission ratio estimate "
              f"{rep.kv_ratio_estimate:.2f}x"
              + (f", reclaimed {rep.reclaimed_bytes} B via degrade ladder"
                 if rep.reclaimed_bytes else ""))
    if prefix_share:
        proj = sum(r.kv_projected_bytes for r in rep.records)
        novel = sum(r.kv_charged_bytes for r in rep.records)
        print(f"[serve] prefix share: admission charged {novel} of {proj} "
              f"projected KV bytes ({proj - novel} B already resident as "
              f"shared pages)")
    if rep.n_devices > 1:
        print(f"[serve] fleet: {rep.n_devices} devices "
              f"(placement {placement or 'hash-stripe'}), "
              f"skew {rep.fleet_skew:.2f}x max/mean moved bytes")
    print(f"[serve] tier after retirement: stored {d.dram_bytes_stored} B, "
          f"{d.blocks} blocks (retired requests freed their namespaces)")
    return sched, rep


def main():
    ap = argparse.ArgumentParser(
        epilog=EPILOG, formatter_class=argparse.RawDescriptionHelpFormatter,
    )
    ap.add_argument("--arch", default="qwen2-0.5b", choices=sorted(ARCHS))
    ap.add_argument("--full-width", action="store_true",
                    help="serve the architecture at its published widths "
                         "(default: the reduced smoke config, sized for "
                         "the CPU)")
    ap.add_argument("--device", default="trace",
                    choices=["plain", "gcomp", "trace"])
    ap.add_argument("--tokens", type=int, default=32)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--streams", type=int, default=1,
                    help="sequences sharing one tier device queue")
    ap.add_argument("--sync-io", action="store_true",
                    help="serialize spill readback (disable the async queue)")
    ap.add_argument("--pnm-topk", type=int, default=None,
                    help="PNM read mode: device-side top-K gather replaces "
                         "full spill readback — the device scores spilled "
                         "pages on the reduced score_view plane subset and "
                         "ships only the K winners (K >= spilled pages is "
                         "bit-identical to the classic path); default off")
    ap.add_argument("--importance", default="recency",
                    choices=["recency", "attention"],
                    help="page-importance signal: commit recency (default) "
                         "or accumulated attention mass fed through "
                         "KVPagePool.update_importance each boundary")
    ap.add_argument("--lossless-only", action="store_true")
    ap.add_argument("--num-requests", type=int, default=0,
                    help="run the continuous-batching scheduler on a "
                         "synthetic trace of this many requests")
    ap.add_argument("--arrival-rate", type=float, default=0.5,
                    help="offered load, requests per decode round")
    ap.add_argument("--arrival-kind", default="poisson",
                    choices=["poisson", "bursty"])
    ap.add_argument("--max-batch", type=int, default=2,
                    help="scheduler batch slots (active requests)")
    ap.add_argument("--kv-capacity", type=int, default=0,
                    help="KV admission capacity in bytes (0 = unlimited)")
    ap.add_argument("--capacity-model", default="logical",
                    choices=["logical", "physical"],
                    help="admit against logical BF16 bytes or the "
                         "residency ledger's physical (post-compression) "
                         "footprint")
    ap.add_argument("--degrade-ladder", default="none",
                    help="precision-elastic reclamation ladder: 'none', "
                         "'default' (man4,man2,man0) or a comma list of "
                         "view names; blocked admissions shed cold "
                         "pages' mantissa planes in place before "
                         "stalling (requires --capacity-model physical)")
    ap.add_argument("--prefix-share", action="store_true",
                    help="store identical completed prompt-prefix KV "
                         "pages once (content-addressed shared. "
                         "namespace, refcounted ledger, copy-on-write "
                         "at divergence) and charge admission only the "
                         "novel-KV projection")
    ap.add_argument("--share-prefix-len", type=int, default=0,
                    help="leading prompt tokens shared verbatim by every "
                         "synthetic request (a common system prompt); "
                         "0 = fully independent prompts")
    ap.add_argument("--slo-ttft-ms", type=float, default=None,
                    help="TTFT SLO target in modeled ms; with either SLO "
                         "flag the continuous-batching report includes "
                         "the fraction of requests meeting every "
                         "configured target")
    ap.add_argument("--slo-tpot-ms", type=float, default=None,
                    help="TPOT SLO target in modeled ms per output token "
                         "(single-token requests have no inter-token gap "
                         "and can only miss on TTFT)")
    ap.add_argument("--shards", type=int, default=0,
                    help="serve against a fleet of N tier devices behind "
                         "one ShardedTierStore front-end (each with its "
                         "own link pipes and busy clock); 0 defers to "
                         "the TRACE_SHARDS env var, 1 pins a single "
                         "device")
    ap.add_argument("--placement", default=None,
                    choices=["hash-stripe", "namespace",
                             "replicate-weights"],
                    help="fleet placement policy (with --shards > 1): "
                         "hash-stripe spreads pages by key hash, "
                         "namespace pins whole request namespaces per "
                         "shard, replicate-weights copies TENSOR writes "
                         "to every shard and fans reads out to the "
                         "least-busy replica")
    ap.add_argument("--sanitize", action="store_true",
                    help="run the tier device with the accounting "
                         "sanitizer on: every commit boundary re-checks "
                         "the residency ledger, receipt conservation, "
                         "busy-clock monotonicity and retire cleanup "
                         "(same as TRACE_SANITIZE=1)")
    args = ap.parse_args()
    enable_compile_cache()
    print(device_line())
    ladder = parse_degrade_ladder(args.degrade_ladder)
    if ladder and args.capacity_model != "physical":
        raise SystemExit(
            "--degrade-ladder requires --capacity-model physical: "
            "reclamation frees stored bytes, which the logical "
            "projection never looks at"
        )
    if args.share_prefix_len and not args.prefix_share:
        print("[serve] note: --share-prefix-len shapes the trace only; "
              "add --prefix-share to actually dedup the shared pages")
    if args.num_requests > 0:
        if args.streams > 1:
            print("[serve] note: --streams is ignored in continuous-"
                  "batching mode (concurrency comes from --max-batch)")
        serve_continuous(
            arch=args.arch, smoke=not args.full_width, device=args.device,
            num_requests=args.num_requests, arrival_rate=args.arrival_rate,
            arrival_kind=args.arrival_kind, max_batch=args.max_batch,
            prompt_len=args.prompt_len, n_tokens=args.tokens,
            batch=args.batch, kv_capacity_bytes=args.kv_capacity or None,
            capacity_model=args.capacity_model,
            degrade_ladder=ladder,
            prefix_share=args.prefix_share,
            share_prefix_len=args.share_prefix_len,
            async_io=not args.sync_io, lossless_only=args.lossless_only,
            sanitize=args.sanitize or None,
            shards=args.shards or None, placement=args.placement,
            slo_ttft_s=(args.slo_ttft_ms / 1e3
                        if args.slo_ttft_ms is not None else None),
            slo_tpot_s=(args.slo_tpot_ms / 1e3
                        if args.slo_tpot_ms is not None else None),
            pnm_topk=args.pnm_topk, importance=args.importance,
        )
        return
    if args.slo_ttft_ms is not None or args.slo_tpot_ms is not None:
        print("[serve] note: --slo-ttft-ms/--slo-tpot-ms apply to "
              "continuous-batching mode (--num-requests N)")
    if args.prefix_share:
        print("[serve] note: --prefix-share applies to continuous-"
              "batching mode (--num-requests N); single/multi-stream "
              "runs have no cross-request reuse")
    serve(arch=args.arch, smoke=not args.full_width, device=args.device,
          n_tokens=args.tokens,
          prompt_len=args.prompt_len, batch=args.batch,
          streams=args.streams, async_io=not args.sync_io,
          lossless_only=args.lossless_only,
          sanitize=args.sanitize or None,
          shards=args.shards or None, placement=args.placement,
          pnm_topk=args.pnm_topk, importance=args.importance)


if __name__ == "__main__":
    main()
