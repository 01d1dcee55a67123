"""The readers of the program's own spans and counters (``repro.obs``):
their numbers on a made-up record, ``None`` where the program recorded
nothing or has no recorder, and names that cannot collide with the
harness's spans in a trace."""

import sys

import pytest
from servebench_fixtures import REPO  # noqa: F401  (puts servebench on the path)
from servebench import program, spec, tracefile
from servebench.spans import SPANS

from repro import obs

CELL = "qwen2-0.5b.spill"
NEW = ("commit_pull_share", "commit_encode_share", "commit_readback_share",
       "model_wait_share", "commit_host_mb_per_token")

RECORD = {
    "spans": {
        "commit.pages": {"count": 44, "total_s": 5.0, "self_s": 0.1},
        "commit.pull": {"count": 44, "total_s": 0.5, "self_s": 0.5},
        "tier.encode": {"count": 7, "total_s": 1.0, "self_s": 0.2},
        "commit.issue": {"count": 7, "total_s": 0.2, "self_s": 0.05},
        "commit.drain": {"count": 7, "total_s": 0.3, "self_s": 0.1},
        "commit.apply": {"count": 7, "total_s": 1.5, "self_s": 1.5},
        "model.sync": {"count": 300, "total_s": 0.4, "self_s": 0.4},
    },
    "counters": {"commit.d2h_bytes": 3_000_000, "commit.copy_bytes": 12_000_000,
                 "commit.h2d_bytes": 3_000_000, "sched.tokens": 6,
                 "kernel.pack.device": 7},
}


class Ctx:
    window_s = 10.0


def _read(name):
    return spec.load(CELL).reader(name)(Ctx)


def test_new_metrics_are_declared_for_the_spill_cell():
    cell = spec.load(CELL)
    got = {m["name"]: m for m in cell.per_layer()}
    for name in NEW:
        assert got[name]["source"] == "program_span"
        assert got[name]["moves"] == "tok_s"
        assert got[name]["workloads"] == [CELL]


def test_readers_on_a_record(monkeypatch):
    monkeypatch.setattr(program, "record", lambda: RECORD)
    assert _read("commit_pull_share") == pytest.approx(5.0)
    assert _read("commit_encode_share") == pytest.approx(10.0)
    assert _read("commit_readback_share") == pytest.approx(20.0)
    assert _read("model_wait_share") == pytest.approx(4.0)
    assert _read("commit_host_mb_per_token") == pytest.approx(3.0)


def test_a_span_the_slice_never_opened_reads_zero(monkeypatch):
    rec = {"spans": {"commit.pages": RECORD["spans"]["commit.pages"]},
           "counters": {"sched.tokens": 4}}
    monkeypatch.setattr(program, "record", lambda: rec)
    assert _read("commit_readback_share") == 0.0
    assert _read("commit_host_mb_per_token") == 0.0


def test_no_tokens_no_bytes_per_token(monkeypatch):
    rec = {"spans": RECORD["spans"], "counters": {"commit.d2h_bytes": 5}}
    monkeypatch.setattr(program, "record", lambda: rec)
    assert _read("commit_host_mb_per_token") is None


def test_an_empty_record_reads_none():
    obs.reset()
    assert program.record() is None
    assert [_read(n) for n in NEW] == [None] * len(NEW)


def test_a_program_without_the_recorder_reads_none(monkeypatch):
    """What the readers meet in a checkout from before ``repro.obs``."""
    import repro

    obs.enable()
    with obs.span("commit.pull"):
        pass
    obs.disable()
    assert program.record() is not None
    monkeypatch.delattr(repro, "obs")
    monkeypatch.setitem(sys.modules, "repro.obs", None)
    assert program.record() is None
    assert [_read(n) for n in NEW] == [None] * len(NEW)
    obs.reset()


def test_recorded_spans_reach_the_reader():
    obs.reset()
    obs.enable()
    try:
        with obs.span("commit.pull"):
            pass
    finally:
        obs.disable()
    rec = program.record()
    assert rec["spans"]["commit.pull"]["count"] == 1
    obs.reset()


def test_gap_goes_to_the_innermost_span_a_program_span_included():
    hs = tracefile._HostSpans([("sched.step", 0, 200),
                               ("engine.commit", 10, 100),
                               ("commit.pages", 11, 99),
                               ("commit.apply", 40, 60)])
    assert hs.at(50) == "commit.apply"
    assert hs.at(20) == "commit.pages"
    assert hs.at(150) == "sched.step"
    assert hs.at(50, {"sched.step", "engine.commit"}) == "engine.commit"


def test_program_span_names_never_reuse_the_harness_names():
    harness = {s[3] for s in SPANS} | {tracefile.WINDOW}
    assert not harness & set(obs.SPAN_NAMES)
    assert len(set(obs.SPAN_NAMES)) == len(obs.SPAN_NAMES)
