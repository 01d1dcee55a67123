"""Serving runtime: paged KV spill → tier round-trip → decode integrity,
sync and async I/O, single- and multi-stream."""

import numpy as np
import pytest

from repro import obs
from repro.core.precision import FULL
from repro.runtime import (
    MultiStreamEngine, PAPER_POLICY, KVPagePool, ServeEngine,
)
from repro.runtime import serving
from repro.runtime.paging import LOSSLESS_POLICY, PagePolicy

pytestmark = pytest.mark.slow   # model-forward module


@pytest.fixture(scope="module")
def engine_pair(smoke_model):
    """Smoke cfg + params shared across the serving tests."""
    return smoke_model("qwen2-0.5b")


def _run(cfg, params, device, policy, n=12, budget=1 << 12, **kw):
    eng = ServeEngine(
        cfg, params, max_seq=96, batch=1, page_tokens=16,
        hbm_kv_budget=budget, device_kind=device, policy=policy, **kw,
    )
    prompt = np.arange(48, dtype=np.int32).reshape(1, 48) % cfg.vocab
    toks = eng.generate(prompt, n)
    return eng, toks


def test_lossless_trace_matches_plain_generation(engine_pair):
    """Byte-exact KV round-trip ⇒ identical greedy generations (the paper's
    §III-D correctness invariant, end to end)."""
    cfg, params = engine_pair
    _, t_plain = _run(cfg, params, "plain", LOSSLESS_POLICY)
    _, t_trace = _run(cfg, params, "trace", LOSSLESS_POLICY)
    np.testing.assert_array_equal(t_plain, t_trace)


def test_spill_and_compression_happen(engine_pair):
    cfg, params = engine_pair
    eng, _ = _run(cfg, params, "trace", LOSSLESS_POLICY)
    s = eng.stats()
    assert s.spilled_pages > 0
    assert s.tier_dram_read > 0
    assert s.kv_compression_ratio > 1.05  # bit-plane + lz4 on real KV


def test_policy_views_reduce_dram_reads(engine_pair):
    cfg, params = engine_pair
    e_full, _ = _run(cfg, params, "trace", LOSSLESS_POLICY)
    e_pol, _ = _run(cfg, params, "trace", PAPER_POLICY)
    # elastic policy fetches fewer planes for cold pages
    assert e_pol.stats().tier_dram_read < e_full.stats().tier_dram_read


def test_policy_generation_stays_sane(engine_pair):
    """Reduced-precision cold pages must not derail generation (tokens in
    vocab, no crash); quality deltas are measured in benchmarks."""
    cfg, params = engine_pair
    _, toks = _run(cfg, params, "trace", PAPER_POLICY)
    assert toks.min() >= 0 and toks.max() < cfg.vocab


def test_kv_through_tier_roundtrip(engine_pair):
    cfg, params = engine_pair
    eng, _ = _run(cfg, params, "trace", LOSSLESS_POLICY)
    kv = eng.kv_through_tier(0, "k")
    assert kv.size > 0 and kv.dtype == np.uint16


def test_page_pool_importance_eviction():
    pool = KVPagePool("trace", page_tokens=8, hbm_budget_bytes=8 * 64 * 2 * 2)
    rng = np.random.default_rng(0)
    for i in range(6):
        page = rng.normal(size=(8, 64)).astype(np.float32)
        import ml_dtypes

        u16 = page.astype(ml_dtypes.bfloat16).view(np.uint16)
        pool.append_page(0, "k", i * 8, u16, importance=float(i))
    # low-importance pages must have spilled first
    resident = [p.start for p in pool._pages if p.resident is not None]
    spilled = [p.start for p in pool._pages if p.resident is None]
    assert len(spilled) == 4 and max(spilled) < min(resident)
    out = pool.read_layer(0, "k")
    assert out.shape == (48, 64)


@pytest.mark.parametrize("copy", [True, False])
def test_page_pool_keeps_handed_over_pages(copy):
    """``copy=False`` keeps the caller's array as the resident page."""
    pool = KVPagePool("trace", page_tokens=8, hbm_budget_bytes=1 << 20)
    u16 = np.arange(8 * 64, dtype=np.uint16).reshape(8, 64)
    pool.append_pages([(0, "k", 0, u16, 0.0)], copy=copy)
    page = pool._pages[0].resident
    assert (page is u16) is not copy
    np.testing.assert_array_equal(page, u16)


def test_commit_pulls_in_bounded_slices(engine_pair, monkeypatch):
    """A commit over more windows than one pull takes brings them in
    several device slices; the bytes pulled, the pages and the tokens
    are those of one slice."""
    cfg, params = engine_pair
    runs = []
    for limit in (serving._PULL_TOKENS, 16):
        monkeypatch.setattr(serving, "_PULL_TOKENS", limit)
        obs.reset()
        obs.enable()
        try:
            eng, toks = _run(cfg, params, "trace", LOSSLESS_POLICY)
        finally:
            obs.disable()
        runs.append((eng, toks, obs.snapshot()))
    (one, t_one, s_one), (many, t_many, s_many) = runs
    np.testing.assert_array_equal(t_one, t_many)
    # the 48-token prompt completes 3 windows: 1 pull, then 3
    assert s_one["spans"]["commit.pull"]["count"] == 1
    assert s_many["spans"]["commit.pull"]["count"] == 3
    assert (s_one["counters"]["commit.d2h_bytes"]
            == s_many["counters"]["commit.d2h_bytes"])
    assert many.stats().spilled_pages == one.stats().spilled_pages > 0
    for layer in range(cfg.n_layers):
        for kind in ("k", "v"):
            np.testing.assert_array_equal(one.kv_through_tier(layer, kind),
                                          many.kv_through_tier(layer, kind))


def test_policy_rank_views():
    pol = PagePolicy()
    views = [pol.view_for_rank(r).name for r in range(12)]
    assert views[:5] == ["bf16"] * 5
    assert views[5:8] == ["man4"] * 3
    assert views[8:] == ["man0"] * 4
    assert pol.avg_bits(10) == (5 * 16 + 3 * 13 + 2 * 9) / 10


# ---------------------------------------------------------------------------
# async I/O overlap + multi-stream serving
# ---------------------------------------------------------------------------

def test_async_io_matches_sync_engine_lossless(engine_pair):
    """With lossless readback, overlapping spill I/O with decode must not
    change a single token, and total tier traffic must match the
    serialized engine exactly (only latency accounting differs)."""
    cfg, params = engine_pair
    e_sync, t_sync = _run(cfg, params, "trace", LOSSLESS_POLICY,
                          async_io=False)
    e_async, t_async = _run(cfg, params, "trace", LOSSLESS_POLICY,
                            async_io=True)
    np.testing.assert_array_equal(t_sync, t_async)
    ss, sa = e_sync.stats(), e_async.stats()
    assert (ss.tier_dram_read, ss.tier_link_out, ss.tier_dram_stored) == \
        (sa.tier_dram_read, sa.tier_link_out, sa.tier_dram_stored)
    assert sa.tier_io_service_s > 0
    assert sa.tier_io_queue_delay_s >= 0


def test_many_streams_match_sequential_engines(engine_pair):
    """N streams sharing ONE device queue generate the same logits/tokens
    as N engines run one after another, and the summed per-stream receipt
    traffic equals the shared device totals field-for-field."""
    cfg, params = engine_pair
    n_streams, n_tok = 3, 6
    prompts = [
        ((np.arange(48) * (i + 1) + i) % cfg.vocab)
        .astype(np.int32).reshape(1, 48)
        for i in range(n_streams)
    ]

    multi = MultiStreamEngine(
        cfg, params, n_streams, device_kind="trace", max_seq=96, batch=1,
        page_tokens=16, hbm_kv_budget=1 << 12, policy=PAPER_POLICY,
    )
    toks_multi = multi.generate(prompts, n_tok)

    for i in range(n_streams):
        eng = ServeEngine(
            cfg, params, max_seq=96, batch=1, page_tokens=16,
            hbm_kv_budget=1 << 12, device_kind="trace",
            policy=PAPER_POLICY, key_prefix=f"s{i}.",
        )
        toks_solo = eng.generate(prompts[i], n_tok)
        np.testing.assert_array_equal(toks_multi[i], toks_solo)

    # per-stream receipts conserve the shared device's aggregate traffic
    d = multi.device_stats()
    summed = {
        f: sum(getattr(t, f)
               for eng in multi.streams
               for t in eng.pool.page_traffic.values())
        for f in ("dram_bytes_read", "dram_bytes_written",
                  "link_bytes_in", "link_bytes_out", "index_bytes")
    }
    assert summed == {f: getattr(d, f) for f in summed}
    # streams are namespaced: no key collisions on the shared device
    keys = [k for eng in multi.streams for k in eng.pool.page_traffic]
    assert len(keys) == len(set(keys))
    assert multi.throughput_ceiling() > 0
    # sharing one window actually coalesces: some receipt waited behind
    # another stream's request on the shared pipes
    assert sum(s.tier_io_queue_delay_s for s in multi.stats()) > 0
