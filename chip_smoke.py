#!/usr/bin/env python3
"""Bring-up check: the served path end to end on one TPU chip.

Serves qwen2-0.5b at its published widths (24 layers, d_model 896,
vocab 151936; random bf16 weights from ``--seed``) through the normal
entry points — ``launch.serve.serve`` and ``serve_continuous`` over
``ServeEngine`` → ``KVPagePool`` → ``TierStore`` — and checks what comes
out.  Run it from the root of a checkout, on a machine with a TPU:

    python chip_smoke.py

Phases, in order; any failure exits non-zero:

1. device   — JAX must report a TPU.  There is no CPU fallback.
2. parity   — on real KV slabs from a full-width prefill, the device
              paths of the bit-plane pack, the KV transform with its pack,
              the LZ4 match scan and the PNM page scores return the same
              bytes as their numpy twins.
3. serve    — under the lossless policy the ``trace`` device (pages
              spill through the bit-plane + LZ4 tier) gives the same
              greedy tokens as ``plain``, and a PNM gather with k ≥ the
              spilled pages gives the same tokens again.
4. schedule — ``ServeScheduler`` finishes every request and the tier
              holds 0 bytes after retirement.

Throughout phases 3-4 every encode and gather in the tier must take the
compiled device path (the ``kernel.<kernel>.<path>`` counters of
``repro.obs``).  Times are the benchmark's (``benchmarks/serve``).  The
last line of output is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": 1}}``.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import ml_dtypes  # noqa: E402
import numpy as np  # noqa: E402

from repro import obs  # noqa: E402
from repro.compile_cache import enable_compile_cache  # noqa: E402
from repro.configs import ARCHS, smoke_config  # noqa: E402
from repro.core import codec  # noqa: E402
from repro.core.bitplane import BLOCK_ELEMS  # noqa: E402
from repro.core.kv_transform import kv_forward_batch  # noqa: E402
from repro.core.tier import BitplaneLayout  # noqa: E402
from repro.kernels import dispatch  # noqa: E402
from repro.kernels.bitplane import (  # noqa: E402
    kv_pack_planes_slab, pack_planes_slab,
)
from repro.kernels.lz4 import match_events_slab  # noqa: E402
from repro.kernels.pnm_score import page_scores_u16  # noqa: E402
from repro.launch.serve import serve, serve_continuous  # noqa: E402
from repro.models import init_cache, init_params  # noqa: E402
from repro.runtime.serving import _jit_step  # noqa: E402

ARCH = "qwen2-0.5b"
PAGE_TOKENS = 16
KERNELS = ("kv_pack", "lz4", "score")


def model(smoke: bool, seed: int):
    """``(cfg, params)`` of the served architecture, random weights."""
    cfg = smoke_config(ARCHS[ARCH]) if smoke else ARCHS[ARCH]
    return cfg, init_params(cfg, jax.random.PRNGKey(seed))


def kv_pages(cfg, params, prompt_len: int, n_tokens: int, batch: int,
             seed: int):
    """Prefill once and cut the K cache into the tier's uint16 pages,
    exactly as ``ServeEngine._commit_pages`` does.  The cache is sized
    as ``serve`` sizes it, so the compiled prefill is shared."""
    rng = np.random.default_rng(seed)
    tokens = rng.integers(0, cfg.vocab, (batch, prompt_len)).astype(np.int32)
    cache = init_cache(cfg, batch, prompt_len + n_tokens + PAGE_TOKENS)
    logits, cache = _jit_step(
        cfg, params, {"tokens": jnp.asarray(tokens),
                      "cache_pos": jnp.int32(0)}, cache)
    assert np.isfinite(np.asarray(logits, dtype=np.float32)).all(), \
        "prefill logits are not finite"
    k = np.asarray(cache["layers"]["k"])          # (L, B, S, KV, hd) bf16
    return [
        np.ascontiguousarray(
            k[layer, :, s : s + PAGE_TOKENS].reshape(PAGE_TOKENS * batch, -1)
        ).view(np.uint16)
        for layer in range(k.shape[0])
        for s in range(0, prompt_len - PAGE_TOKENS + 1, PAGE_TOKENS)
    ]


def phase_parity(pages) -> None:
    """Device path vs numpy twin, byte for byte, on one encode slab."""
    n = BitplaneLayout.ENCODE_SLAB_ELEMS
    flat = np.concatenate([p.ravel() for p in pages])[:n]
    dev = pack_planes_slab(flat, force="pallas")
    ref = pack_planes_slab(flat, force="numpy")
    assert dev.dtype == ref.dtype and np.array_equal(dev, ref), "pack differs"
    print(f"[parity] pack: {flat.size} elems -> {ref.shape} planes, "
          f"device == numpy")

    # one slab of the tier's KV windows, transformed and packed
    windows = [p[i : i + PAGE_TOKENS] for p in pages
               for i in range(0, p.shape[0], PAGE_TOKENS)]
    windows = windows[: n // windows[0].size]
    kv_dev, beta_dev = kv_pack_planes_slab(
        windows, interpret=jax.default_backend() != "tpu")
    streams, metas = kv_forward_batch(np.stack(windows))
    kv_ref = pack_planes_slab(streams.ravel(), force="numpy")
    assert np.array_equal(kv_dev, kv_ref), "kv pack differs"
    assert np.array_equal(beta_dev, np.stack([m.beta for m in metas])), \
        "kv betas differ"
    print(f"[parity] kv_pack: {len(windows)} windows of "
          f"{windows[0].shape} -> {kv_ref.shape} planes, device == numpy")

    # the tier's streams: one per (plane, block), BLOCK_ELEMS // 8 bytes
    slab = ref.reshape(-1)
    blk = BLOCK_ELEMS // 8
    starts = np.arange(0, slab.size, blk)
    ends = starts + blk
    ev_dev = match_events_slab(slab, starts, ends, force="device")
    ev_ref = match_events_slab(slab, starts, ends, force="numpy")
    for a, b in zip(ev_dev, ev_ref):
        assert np.array_equal(a, b), "lz4 match events differ"
    pay_dev = codec.compress_slab(slab, starts, ends, "lz4", force="device")
    pay_ref = codec.compress_slab(slab, starts, ends, "lz4", force="numpy")
    assert pay_dev == pay_ref, "lz4 payloads differ"
    print(f"[parity] lz4: {starts.size} streams, {ev_ref[0].size} matches, "
          f"{sum(map(len, pay_ref[0]))} B payload, device == numpy")

    channels = pages[0].shape[-1]
    digest = (pages[-1].view(ml_dtypes.bfloat16).astype(np.float32)
              .reshape(-1, channels).mean(axis=0))
    s_dev = page_scores_u16(pages, digest, force="pallas")
    s_ref = page_scores_u16(pages, digest, force="numpy")
    assert s_dev.tobytes() == s_ref.tobytes(), "page scores differ"
    assert np.isfinite(s_ref).all()
    print(f"[parity] score: {len(pages)} pages x {channels} channels, "
          f"device == numpy")


def phase_serve(smoke: bool, prompt_len: int, n_tokens: int, batch: int,
                seed: int) -> int:
    """trace == plain == PNM-cover tokens; returns the spilled pages."""
    kw = dict(arch=ARCH, smoke=smoke, prompt_len=prompt_len,
              n_tokens=n_tokens, batch=batch, lossless_only=True, seed=seed,
              shards=1)
    eng, toks_trace = serve(device="trace", **kw)
    spilled = eng.stats().spilled_pages
    assert spilled > 0, "no page spilled to the tier"
    _, toks_plain = serve(device="plain", **kw)
    assert np.array_equal(toks_trace, toks_plain), "trace tokens != plain"
    print(f"[serve] trace tokens == plain tokens ({toks_trace.shape}, "
          f"{spilled} pages spilled)")
    _, toks_pnm = serve(device="trace", pnm_topk=spilled, **kw)
    assert np.array_equal(toks_pnm, toks_trace), "PNM tokens != classic"
    print(f"[serve] PNM top-{spilled} tokens == classic readback tokens")
    return spilled


def phase_schedule(smoke: bool, num_requests: int, prompt_len: int,
                   n_tokens: int, batch: int, seed: int) -> None:
    sched, rep = serve_continuous(
        arch=ARCH, smoke=smoke, num_requests=num_requests,
        prompt_len=prompt_len, n_tokens=n_tokens, batch=batch, seed=seed,
        shards=1)
    assert len(rep.records) == num_requests, "requests left unfinished"
    assert all(r.tokens.shape == (batch, n_tokens) for r in rep.records)
    d = sched.device_stats()
    assert d.dram_bytes_stored == 0 and d.blocks == 0, \
        f"tier holds {d.dram_bytes_stored} B after retirement"
    print(f"[schedule] {num_requests} requests finished, tier holds "
          f"{d.dram_bytes_stored} B in {d.blocks} blocks")


def _check_device_paths(counters: dict) -> None:
    """Every tier encode and gather took the compiled kernel, by the
    ``kernel.<kernel>.<path>`` counters of ``repro.obs``."""
    calls = {k: n for k, n in counters.items() if k.startswith("kernel.")}
    other = {k: n for k, n in calls.items()
             if not k.endswith(f".{dispatch.DEVICE}")}
    assert not other, f"tier calls off the device path: {other}"
    on = {k: calls.get(f"kernel.{k}.{dispatch.DEVICE}", 0) for k in KERNELS}
    missing = [k for k, n in on.items() if not n]
    assert not missing, f"no device call of {missing}"
    print("[guard] tier kernel calls, all on the device path: "
          + ", ".join(f"{k}={n}" for k, n in on.items()))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--prompt-len", type=int, default=256)
    ap.add_argument("--tokens", type=int, default=32)
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--requests", type=int, default=4)
    args = ap.parse_args(argv)

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found platform "
              f"{devs[0].platform!r} ({devs[0].device_kind})",
              file=sys.stderr)
        return 2
    cache_dir = enable_compile_cache()
    print(f"[device] kind={devs[0].device_kind} count={len(devs)} "
          f"compile_cache={cache_dir}")

    cfg, params = model(smoke=False, seed=args.seed)
    print(f"[model] {ARCH}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
          f"vocab {cfg.vocab}, {cfg.n_kv_heads} kv heads")
    phase_parity(kv_pages(cfg, params, args.prompt_len, args.tokens,
                          args.batch, args.seed))
    obs.reset()
    phase_serve(False, args.prompt_len, args.tokens, args.batch, args.seed)
    phase_schedule(False, args.requests, args.prompt_len, args.tokens,
                   args.batch, args.seed)
    _check_device_paths(obs.snapshot()["counters"])
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
