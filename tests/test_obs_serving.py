"""A served run on the CPU with the recorder on: every span of the served
path records, the commit's parts nest inside it, its total agrees with a
timer around ``_commit_pages`` from outside, and the byte counters equal
what the buffers' sizes and the calls give by hand."""

import sys
from pathlib import Path

import numpy as np
import pytest

from repro import obs
from repro.runtime.paging import LOSSLESS_POLICY
from repro.runtime.serving import ServeEngine, ServeRequest, ServeScheduler

BENCH = Path(__file__).resolve().parents[1] / "benchmarks" / "serve"

PAGE = 16
PROMPT = 32
NEW = (19, 24, 21)        # no request retires right after a page boundary
# every span on this path; the PNM gather and reclaim are off it
PATH_SPANS = set(obs.SPAN_NAMES) - {"tier.gather", "pool.reclaim"}
READBACK = ("commit.issue", "commit.drain", "commit.apply")


@pytest.fixture(scope="module")
def served(smoke_model):
    """One scheduler run with the recorder on and the benchmark's own
    ``_commit_pages`` timer installed; the calls the counters count."""
    if str(BENCH) not in sys.path:
        sys.path.insert(0, str(BENCH))
    from servebench.spans import Spans

    cfg, params = smoke_model("qwen2-0.5b")
    rng = np.random.default_rng(5)
    reqs = [ServeRequest(req_id=i, arrival=0.0,
                         prompt=rng.integers(0, cfg.vocab, (1, PROMPT))
                         .astype(np.int32),
                         max_new_tokens=n, seed=i)
            for i, n in enumerate(NEW)]
    sched = ServeScheduler(cfg, params, max_batch=2, device_kind="trace",
                           policy=LOSSLESS_POLICY, page_tokens=PAGE,
                           hbm_kv_budget=1 << 12)
    commits, applied = [], []
    orig_commit = ServeEngine.__dict__["_commit_pages"]
    orig_apply = ServeEngine.__dict__["_apply_readback"]

    def commit(eng, lo, hi):
        commits.append((lo, hi))
        return orig_commit(eng, lo, hi)

    def apply(eng, pages, data):
        applied.append((len(pages), {p.kind for p in pages}))
        return orig_apply(eng, pages, data)

    spans = Spans()
    spans.on = True
    ServeEngine._commit_pages = commit
    ServeEngine._apply_readback = apply
    spans.install()
    obs.reset()
    obs.enable()
    try:
        rep = sched.run(reqs)
    finally:
        obs.disable()
        spans.uninstall()
        ServeEngine._commit_pages = orig_commit
        ServeEngine._apply_readback = orig_apply
    snap = obs.snapshot()
    obs.reset()
    # one K or V cache: (layers, batch 1, max_seq, kv heads, head dim) bf16
    max_seq = PROMPT + max(NEW) + PAGE
    buf_bytes = cfg.n_layers * max_seq * cfg.n_kv_heads * cfg.head_dim * 2
    return dict(rep=rep, snap=snap, spans=spans, commits=commits,
                applied=applied, buf_bytes=buf_bytes)


def test_every_span_of_the_served_path_records(served):
    got = served["snap"]["spans"]
    assert set(got) == PATH_SPANS
    for s in got.values():
        assert s["count"] > 0 and 0 <= s["self_s"] <= s["total_s"] + 1e-9


def test_commit_parts_fit_inside_the_commit(served):
    t = {k: v["total_s"] for k, v in served["snap"]["spans"].items()}
    parts = t["commit.pull"] + t["tier.encode"] + sum(t[n] for n in READBACK)
    assert parts <= t["commit.pages"]
    assert t["commit.pull"] + t["commit.append"] + sum(
        t[n] for n in READBACK) <= t["commit.pages"]


def test_commit_total_matches_the_outside_timer(served):
    inside = served["snap"]["spans"]["commit.pages"]
    outside = served["spans"]
    assert inside["count"] == outside.count["engine.commit"]
    assert inside["total_s"] == pytest.approx(outside.total["engine.commit"],
                                              rel=0.05)


def test_byte_counters_by_hand(served):
    b = served["buf_bytes"]
    pulls = sum(hi // PAGE > lo // PAGE for lo, hi in served["commits"])
    applies = [a for a in served["applied"] if a[0]]
    pages = sum(n for n, _ in applies)
    kinds = sum(len(k) for _, k in applies)
    c = served["snap"]["counters"]
    assert pulls > 0 and pages > 0
    assert c["commit.d2h_bytes"] == (2 * pulls + kinds) * b
    assert c["commit.copy_bytes"] == pages * b
    assert c["commit.h2d_bytes"] == kinds * b
    assert c["sched.tokens"] == sum(NEW)
    assert c["sched.tokens"] == sum(r.tokens.size for r in served["rep"].records)
