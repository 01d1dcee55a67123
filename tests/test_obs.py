"""``repro.obs``: spans off by default and free there, self and inclusive
times under nesting, counters, and the profiler following."""

import gzip
import json

import jax
import jax.numpy as jnp
import pytest

from repro import obs


class _Annotations:
    """Stands in for ``jax.profiler.TraceAnnotation``: records every
    annotation opened, and says whether a profiler trace runs."""

    traced = False

    def __init__(self):
        self.opened = []
        spy = self

        class Ann:
            def __init__(self, name, **meta):
                self.name, self.meta = name, meta

            def __enter__(self):
                spy.opened.append((self.name, self.meta))

            def __exit__(self, *exc):
                return False

            @staticmethod
            def is_enabled():
                return spy.traced

        self.cls = Ann


@pytest.fixture
def anns(monkeypatch):
    a = _Annotations()
    monkeypatch.setattr(obs, "TraceAnnotation", a.cls)
    monkeypatch.setattr(obs, "StepTraceAnnotation", a.cls)
    yield a
    a.traced = False
    obs.tick(0)          # the profiler has stopped: recording follows
    obs.disable()
    obs.reset()


@pytest.fixture
def clock(monkeypatch):
    """A perf_counter that moves only when the test says."""
    now = [0.0]
    monkeypatch.setattr(obs.time, "perf_counter", lambda: now[0])
    return now


def test_off_records_nothing_and_opens_no_annotation(anns):
    obs.reset()
    ctxs = [obs.span("commit.pull"), obs.span("tier.pack", "r1"),
            obs.tick(3)]
    for c in ctxs:
        with c:
            pass
    assert all(c is ctxs[0] for c in ctxs)       # one shared no-op
    assert anns.opened == []
    assert obs.snapshot()["spans"] == {}


def test_nesting_gives_self_and_inclusive_times(anns, clock):
    obs.enable()
    with obs.span("commit.pages", "r7"):
        clock[0] += 1.0
        with obs.span("commit.pull"):
            clock[0] += 2.0
        with obs.span("commit.append"):
            clock[0] += 0.5
            with obs.span("tier.encode"):
                clock[0] += 4.0
        clock[0] += 0.25
    spans = obs.snapshot()["spans"]
    assert spans["commit.pages"] == {"count": 1, "total_s": 7.75,
                                     "self_s": 1.25}
    assert spans["commit.append"] == {"count": 1, "total_s": 4.5,
                                      "self_s": 0.5}
    assert spans["tier.encode"]["self_s"] == spans["tier.encode"]["total_s"]
    for s in spans.values():
        assert 0 <= s["self_s"] <= s["total_s"]
    inner = sum(spans[n]["total_s"] for n in ("commit.pull", "commit.append"))
    assert inner <= spans["commit.pages"]["total_s"]
    # every span of the request carries its tag, inherited from the parent
    assert [m for _, m in anns.opened] == [{"req": "r7"}] * 4
    assert [n for n, _ in anns.opened] == ["commit.pages", "commit.pull",
                                           "commit.append", "tier.encode"]


def test_repeats_add_up(anns, clock):
    obs.enable()
    for _ in range(3):
        with obs.span("model.sync"):
            clock[0] += 0.5
    assert obs.snapshot()["spans"]["model.sync"] == {
        "count": 3, "total_s": 1.5, "self_s": 1.5}


def test_undeclared_span_is_refused_when_on(anns):
    with obs.span("engine.commit"):      # off: not even looked at
        pass
    obs.enable()
    with pytest.raises(ValueError, match="SPAN_NAMES"):
        obs.span("engine.commit")


def test_counters_are_always_on_and_reset_clears_both(anns, clock):
    obs.reset()
    obs.count("commit.d2h_bytes", 100)
    obs.count("commit.d2h_bytes", 28)
    obs.count("sched.tokens")
    obs.enable()
    with obs.span("commit.apply"):
        clock[0] += 1.0
    snap = obs.snapshot()
    assert snap["counters"] == {"commit.d2h_bytes": 128, "sched.tokens": 1}
    assert json.loads(json.dumps(snap)) == snap          # plain dicts
    snap["counters"]["sched.tokens"] = 99                # a copy
    assert obs.snapshot()["counters"]["sched.tokens"] == 1
    obs.reset()
    assert obs.snapshot() == {"spans": {}, "counters": {}}


def test_recording_follows_the_profiler(anns, clock):
    obs.count("sched.tokens", 5)
    anns.traced = True
    with obs.tick(11):                    # a trace started: fresh record
        with obs.span("sched.admit"):
            clock[0] += 1.0
    snap = obs.snapshot()
    assert snap["counters"] == {}
    assert snap["spans"]["sched.tick"]["total_s"] == 1.0
    assert anns.opened[0] == ("sched.tick", {"step_num": 11})
    anns.traced = False
    with obs.tick(12):                    # stopped: off again, record kept
        with obs.span("sched.admit"):
            clock[0] += 1.0
    assert obs.snapshot()["spans"]["sched.tick"]["count"] == 1
    obs.enable()                          # enable() holds without a trace
    with obs.tick(13):
        pass
    assert obs.snapshot()["spans"]["sched.tick"]["count"] == 2


def test_spans_land_in_a_real_profiler_trace(tmp_path):
    """Under ``jax.profiler`` the spans are annotations on the trace's
    host clock, tagged with their request; after the trace, off again."""
    x = jnp.ones((64, 64))
    jax.profiler.start_trace(str(tmp_path), create_perfetto_trace=True)
    try:
        with obs.tick(1):
            with obs.span("model.decode", "r4"):
                (x @ x).block_until_ready()
            with obs.span("commit.pull"):
                pass
    finally:
        jax.profiler.stop_trace()
    spans = obs.snapshot()["spans"]
    assert set(spans) == {"sched.tick", "model.decode", "commit.pull"}
    with obs.tick(2):
        with obs.span("commit.pull"):
            pass
    assert obs.snapshot()["spans"]["commit.pull"]["count"] == 1
    obs.reset()
    path = next(tmp_path.rglob("perfetto_trace.json.gz"))
    events = json.loads(gzip.decompress(path.read_bytes()))["traceEvents"]
    names = [e.get("name", "") for e in events]
    assert any(n.startswith("model.decode") for n in names)
    assert any(n.startswith("commit.pull") for n in names)
    decode = next(e for e in events if e.get("name", "").startswith("model.decode"))
    assert "r4" in json.dumps(decode)
