"""The check that decides ``correct``: the control and each fault that a
served cell can have come out not correct."""

import time

import numpy as np
import pytest
from servebench_fixtures import TINY, make_root


@pytest.fixture
def root(tmp_path):
    return make_root(tmp_path / "bench")


def _run(root, control=False):
    from servebench import cell, spec

    return cell.run(spec.load(TINY, root), 987654321, 1.0, False,
                    time.perf_counter(), control=control)


def test_control_and_fault_read_above_the_limits(root):
    out = _run(root, control=True)
    chk = out["check"]
    assert out["correct"] is True
    rmse, gap = chk["logit_rmse"], chk["max_logit_gap"]
    assert chk["control_logit_rmse"]["value"] > rmse["limit"] > rmse["value"]
    assert chk["control_logit_rmse"]["value"] >= 3 * rmse["value"]
    assert chk["fault_max_logit_gap"]["value"] > gap["limit"] >= gap["value"]


def test_token_altered_where_produced_is_caught(root, monkeypatch):
    from repro.runtime import serving

    orig = serving._sample_next
    calls = [0]

    def altered(logits, rng, greedy):
        calls[0] += 1
        nxt = orig(logits, rng, greedy)
        return (nxt + 1) % logits.shape[-1] if calls[0] % 5 == 0 else nxt

    monkeypatch.setattr(serving, "_sample_next", altered)
    assert _run(root)["correct"] is False


def test_step_that_leaves_its_state_unchanged_is_caught(root, monkeypatch):
    from repro.runtime import serving

    orig = serving._jit_step

    def stale(cfg, params, batch, cache):
        logits, new = orig(cfg, params, batch, cache)
        return logits, (cache if batch["tokens"].shape[1] == 1 else new)

    monkeypatch.setattr(serving, "_jit_step", stale)
    assert _run(root)["correct"] is False


def test_pages_altered_in_the_tier_readback_are_caught(root, monkeypatch):
    from repro.runtime.paging import KVPagePool

    orig = KVPagePool.drain_reads
    served = [0]

    def zeroed(self, tickets):
        data = orig(self, tickets)
        served[0] += len(data)
        return [np.zeros_like(d) for d in data]

    monkeypatch.setattr(KVPagePool, "drain_reads", zeroed)
    assert _run(root)["correct"] is False
    assert served[0] > 0


def test_sample_holds_the_longest_and_follows_the_seed():
    from servebench.correct import sample

    class R:
        def __init__(self, rid, n):
            self.rid, self.n_out = rid, n

    reqs = [R(i, n) for i, n in enumerate([5, 40, 7, 9, 12, 3])]
    a, b = sample(reqs, 3, 11), sample(reqs, 3, 11)
    assert a[0].n_out == 40 and len(a) == 3
    assert [r.rid for r in a] == [r.rid for r in b]
    assert len({tuple(r.rid for r in sample(reqs, 3, s)) for s in range(20)}) > 1
    assert np.all([r in reqs for r in a])
