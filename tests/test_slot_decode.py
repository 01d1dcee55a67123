"""Batched decode over a shared slot cache: a ``(B,)`` ``cache_pos`` step
agrees row by row with batch-1 steps, and ``ServeScheduler`` decodes
every active request in one step without changing a token."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.models import decode_step, init_cache
from repro.runtime import ServeEngine, ServeRequest, ServeScheduler
from repro.runtime import serving
from repro.runtime.paging import LOSSLESS_POLICY, PAPER_POLICY

pytestmark = pytest.mark.slow   # model-forward module

_step = jax.jit(decode_step, static_argnums=0)


@pytest.fixture(scope="module")
def qwen(smoke_model):
    return smoke_model("qwen2-0.5b")


def _solo(cfg, params, req, max_seq, policy=LOSSLESS_POLICY, **kw):
    return ServeEngine(
        cfg, params, max_seq=max_seq, batch=1, page_tokens=16,
        hbm_kv_budget=1 << 12, device_kind="trace", policy=policy, **kw,
    ).generate(req.prompt, req.max_new_tokens, seed=req.seed)


def _reqs(cfg, prompts, news, arrivals=None, seed=23):
    rng = np.random.default_rng(seed)
    arrivals = arrivals or [0.0] * len(prompts)
    return [ServeRequest(
        req_id=i, arrival=float(a),
        prompt=rng.integers(0, cfg.vocab, (1, p)).astype(np.int32),
        max_new_tokens=n, seed=50 + i)
        for i, (p, n, a) in enumerate(zip(prompts, news, arrivals))]


def _sched(cfg, params, **kw):
    kw.setdefault("max_batch", 2)
    kw.setdefault("policy", LOSSLESS_POLICY)
    return ServeScheduler(cfg, params, device_kind="trace", page_tokens=16,
                          hbm_kv_budget=1 << 12, **kw)


def _counted(sched, reqs):
    obs.reset()
    rep = sched.run(reqs)
    counters = obs.snapshot()["counters"]
    obs.reset()
    return rep, counters


# ---------------------------------------------------------------------------
# the model: per-row positions
# ---------------------------------------------------------------------------

def test_row_positions_match_batch1_steps(qwen):
    """Four rows at ragged positions (one of them a parked row of zeros
    at 0) in one step give each row's logits and cache of its own
    batch-1 step at a scalar position, bit for bit."""
    cfg, params = qwen
    max_seq = 48
    rng = np.random.default_rng(3)
    lens = [5, 17, 31, 0]
    caches = []
    for n in lens:
        c = init_cache(cfg, 1, max_seq)
        if n:
            prompt = rng.integers(0, cfg.vocab, (1, n)).astype(np.int32)
            _, c = _step(cfg, params, {"tokens": jnp.asarray(prompt),
                                       "cache_pos": jnp.int32(0)}, c)
        caches.append(c)
    tokens = rng.integers(0, cfg.vocab, (len(lens), 1)).astype(np.int32)
    solo = [_step(cfg, params, {"tokens": jnp.asarray(tokens[r : r + 1]),
                                "cache_pos": jnp.int32(n)}, caches[r])
            for r, n in enumerate(lens)]
    both = jax.tree.map(lambda *a: jnp.concatenate(a, axis=1), *caches)
    logits, cache = _step(cfg, params, {
        "tokens": jnp.asarray(tokens),
        "cache_pos": jnp.asarray(np.array(lens, np.int32))}, both)
    assert logits.shape[0] == len(lens)
    for r, (lg, c) in enumerate(solo):
        np.testing.assert_array_equal(np.asarray(logits[r]), np.asarray(lg[0]))
        for kind in ("k", "v"):
            np.testing.assert_array_equal(
                np.asarray(cache["layers"][kind][:, r]),
                np.asarray(c["layers"][kind][:, 0]))


# ---------------------------------------------------------------------------
# the scheduler: one step a tick over the slot cache
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("policy,async_io", [
    (LOSSLESS_POLICY, True), (PAPER_POLICY, True), (PAPER_POLICY, False)],
    ids=["lossless", "paper", "paper-sync"])
def test_ragged_requests_reuse_slots_and_match_solo(qwen, policy, async_io):
    """Ragged prompts and outputs, five requests through two slots: each
    retirement frees a slot the next request decodes in, the tier's
    readback lands in the right rows (a lossy policy changes the pages
    it writes back), and every request's tokens are its solo run's."""
    cfg, params = qwen
    reqs = _reqs(cfg, [16, 40, 24, 33, 48], [7, 3, 12, 5, 9],
                 arrivals=[0, 0, 1, 2, 2])
    sched = _sched(cfg, params, policy=policy, async_io=async_io)
    rep, counters = _counted(sched, reqs)
    assert len(rep.records) == 5 and rep.peak_active == 2
    assert sched._slots is not None and sched._slots.rows == 2
    assert counters["model.decode_rows"] == sum(r.max_new_tokens - 1
                                                for r in reqs)
    for req, rec in zip(reqs, rep.records):
        solo = _solo(cfg, params, req, sched.max_seq, policy,
                     async_io=async_io)
        np.testing.assert_array_equal(solo, rec.tokens)


def test_one_slot_decodes_as_today(qwen):
    """``max_batch=1``: a one-row slot cache gives the tokens of a solo
    engine, the per-request path's own reference."""
    cfg, params = qwen
    reqs = _reqs(cfg, [20, 36], [6, 4])
    sched = _sched(cfg, params, max_batch=1)
    rep, counters = _counted(sched, reqs)
    assert counters["model.decode_rows"] == counters["model.decode_batches"] == 8
    for req, rec in zip(reqs, rep.records):
        np.testing.assert_array_equal(
            _solo(cfg, params, req, sched.max_seq), rec.tokens)


@pytest.mark.parametrize("arch", ["zamba2-7b", "deepseek-v2-lite-16b"])
def test_other_cache_layouts_keep_an_engine_cache(smoke_model, arch):
    """A hybrid (recurrent state and a rolling window) or latent (MLA,
    leading dense layers) cache is not batched: each request keeps its
    own cache and batch-1 step, and its tokens stay its solo run's."""
    cfg, params = smoke_model(arch)
    assert not serving.slot_layout(cfg)
    reqs = _reqs(cfg, [16, 24], [3, 4])
    sched = _sched(cfg, params)
    engines = []
    orig = sched._start

    def start(req, rec, slot):
        seq = orig(req, rec, slot)
        engines.append(seq.engine)
        return seq

    sched._start = start
    rep, counters = _counted(sched, reqs)
    assert sched._slots is None
    assert all(e.slots is None and e.cache is not None for e in engines)
    assert "model.decode_batches" not in counters
    for req, rec in zip(reqs, rep.records):
        np.testing.assert_array_equal(
            _solo(cfg, params, req, sched.max_seq), rec.tokens)


def test_one_decode_program_across_active_counts(qwen):
    """Active requests go 1 → 3 → 1 as they arrive and retire, and the
    decode step compiles once: one program for the prompt length, one
    for the slot cache's rows."""
    cfg, params = qwen
    cfg = dataclasses.replace(cfg, name=cfg.name + "-slot-compiles")
    reqs = _reqs(cfg, [24] * 4, [9, 3, 6, 2], arrivals=[0, 1, 1, 5])
    before = serving._jit_step._cache_size()
    sched = _sched(cfg, params, max_batch=3)
    actives = []
    sched.submit(reqs)
    while sched.step():
        actives.append(sched.n_active)
    assert {1, 3} <= set(actives)
    assert serving._jit_step._cache_size() - before == 2


def test_decode_counters(qwen):
    """Three requests from tick 0 in three slots: one batched step a
    tick while any decodes, and the rows that decoded in each."""
    cfg, params = qwen
    news = [4, 7, 5]
    sched = _sched(cfg, params, max_batch=3)
    _, counters = _counted(sched, _reqs(cfg, [16, 16, 32], news))
    assert counters["model.decode_batches"] == max(news) - 1
    assert counters["model.decode_rows"] == sum(n - 1 for n in news)


def test_slot_cache_grows_with_a_longer_request(qwen):
    """A longer request submitted while another decodes lengthens every
    row; the running request keeps the tokens of a solo run at the
    budget it was admitted with, the new one those at the new budget."""
    cfg, params = qwen
    first, longer = _reqs(cfg, [20, 60], [12, 10], arrivals=[0, 3])
    sched = _sched(cfg, params)
    sched.submit([first])
    budget = sched.max_seq
    for _ in range(3):
        sched.step()
    sched.submit([longer])
    rep = sched.run()
    assert sched._slots.max_seq == sched.max_seq > budget
    for req, rec, max_seq in zip((first, longer), rep.records,
                                 (budget, sched.max_seq)):
        np.testing.assert_array_equal(
            _solo(cfg, params, req, max_seq), rec.tokens)
