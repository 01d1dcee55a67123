"""The trace reduction, on a small trace of the spill cell recorded on a
TPU v5e through the harness (0.68 s traced: decode steps, a prefill, the
tier's pack and LZ4 kernels)."""

import gzip
import shutil

import pytest
from servebench_fixtures import HERE

from servebench import peaks, spec, tracefile
from servebench.spans import CALLS, SPANS

FIXTURE = HERE / "data" / "spill_window.xplane.pb.gz"
KIND = "TPU v5 lite"


@pytest.fixture(scope="module")
def red(tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "spill_window.xplane.pb"
    with gzip.open(FIXTURE) as src, open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return tracefile.reduce(path, [s[3] for s in SPANS], CALLS)


def test_window_busy_and_gaps_add_up(red):
    assert red.chips == 1
    assert red.window_s == pytest.approx(0.680913614)
    assert red.busy_s == pytest.approx(0.13068672)
    assert red.busy_s + sum(red.idle_gaps.values()) == pytest.approx(red.window_s)
    assert red.gaps()[0][0] == "engine.commit"


def test_programs_are_told_apart_by_where_they_ran(red):
    decode = red.module_time("jit_decode_step", "engine.decode")
    assert len(decode) == 23
    assert sum(decode) / 23 == pytest.approx(1.6771858695652175e-3)
    assert red.module_time("jit_decode_step", "engine.prefill") == \
        [pytest.approx(2.526882e-3)]
    ops = dict(red.device_ops())
    assert ops["jit_decode_step [engine.decode]"] == pytest.approx(sum(decode))


def test_kernels_carry_their_signature_bytes(red):
    pack = [k for k in red.kernels if k[0] == "pack_planes_pallas"]
    assert len(pack) == 8
    assert {k[3] for k in pack} == {2 * 8 * 128 * 128 * 2}   # u16 in, u8 planes out
    assert sum(k[2] for k in pack) == 24083


def _ctx(red, name="qwen2-0.5b.spill"):
    from servebench.cell import PerLayer
    from servebench.spans import Spans

    cell = spec.load(name)
    return PerLayer(cell, cell.conf, cell.family, KIND, peaks.peak(KIND),
                    red.window_s, Spans(), red)


def test_readers_on_the_recorded_trace(red):
    cell = spec.load("qwen2-0.5b.spill")
    ctx = _ctx(red)
    idle = cell.reader("device_idle_share")(ctx)
    assert idle == pytest.approx((1 - 0.13068672 / 0.680913614) * 100)
    assert cell.reader("decode_step_ms")(ctx) == pytest.approx(1.6771858695652175)
    roof = cell.reader("pack_planes_roofline")(ctx)
    assert roof == pytest.approx(8 * 524288 / 819e9 / 24083e-9 * 100)
    assert 0 < roof < 100


def test_readers_find_nothing_and_say_so(red):
    empty = tracefile.Reduced(window_s=1.0, busy_s=0.5, chips=1, modules=[],
                              kernels=[], idle_gaps={})
    cell = spec.load("qwen2-0.5b.spill")
    ctx = _ctx(empty)
    for name in ("decode_step_ms", "decode_step_roofline",
                 "pack_planes_roofline", "commit_share", "prefill_share",
                 "sched_self_ms_per_tick", "serve_mfu"):
        assert cell.reader(name)(ctx) is None, name


def test_span_attribution_prefers_the_call_span():
    hs = tracefile._HostSpans([("sched.step", 0, 100), ("engine.decode", 10, 60),
                               ("engine.commit", 20, 30)])
    assert hs.at(25) == "engine.commit"
    assert hs.call_at(25, CALLS) == "engine.decode"
    assert hs.call_at(80, CALLS) == "sched.step"
    assert hs.call_at(150, CALLS) == tracefile.OUTSIDE
