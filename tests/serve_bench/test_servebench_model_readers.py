"""The chat cell's readers of model work (``prefill_roofline``,
``decode_roofline``, ``model_mfu``) against hand counts on a made-up
slice, and ``None`` where the slice holds no call or no program."""

import pytest
from servebench_fixtures import REPO  # noqa: F401  (puts servebench on the path)
from servebench import peaks, spec, tracefile, work
from servebench.cell import PerLayer
from servebench.spans import Spans

CELL = "mistral-7b-v0.3.chat"
NEW = {"prefill_roofline": ("device_trace", "ttft_p50_s"),
       "decode_roofline": ("device_trace", "tok_s"),
       "model_mfu": ("program_span", "tok_s")}
KIND = "TPU v5 lite"

# Mistral-7B-v0.3, 8 of 32 layers, by hand
MATMUL = 8 * (2 * 4096 * 4096 + 2 * 4096 * 1024 + 3 * 4096 * 14336) \
    + 32768 * 4096
ATTN = 4 * 8 * 32 * 128                    # per position attended, all layers
HEAD = 2 * 32768 * 4096
PARAMS = 2_013_335_552
KV = 32768                                 # bf16 K and V, bytes per token


def _weights_and_kv(ctx):
    return (PARAMS - 32767 * 4096) * 2 + KV * ctx


def _prefill_flops(n):
    return 2 * MATMUL * n + ATTN * n * (n + 1) // 2 - (n - 1) * HEAD


def _decode_flops(pos):
    return 2 * MATMUL + ATTN * (pos + 1)


def _ctx(calls, modules, window_s=8.0):
    cell = spec.load(CELL)
    spans = Spans()
    spans.calls = list(calls)
    red = tracefile.Reduced(window_s=window_s, busy_s=1.0, chips=1,
                            modules=list(modules), kernels=[], idle_gaps={})
    return cell, PerLayer(cell, cell.conf, cell.family, KIND, peaks.peak(KIND),
                          window_s, spans, red)


# a 2048-token prefill and a 512-token prefill, then two decode steps
CALLS = [(0.0, 0.1, "prefill", 0, 2048), (0.1, 0.2, "prefill", 0, 512),
         (0.2, 0.3, "decode", 2048, 1), (0.3, 0.4, "decode", 512, 1)]
MODULES = [("jit_decode_step", "p2048", "engine.prefill", 0, 75_000_000),
           ("jit_decode_step", "p512", "engine.prefill", 1, 20_000_000),
           ("jit_decode_step", "d1", "engine.decode", 2, 5_460_000),
           ("jit_decode_step", "d1", "engine.decode", 3, 5_440_000),
           ("jit_dynamic_slice", "s", "engine.prefill", 4, 9_000)]


def test_declared_for_the_chat_cell_alone():
    got = {m["name"]: m for m in spec.load(CELL).per_layer()}
    for name, (source, moves) in NEW.items():
        m = got[name]
        assert (m["unit"], m["better"], m["source"], m["moves"]) == \
            ("%", "higher", source, moves)
        assert m["workloads"] == [CELL]
    spill = {m["name"] for m in spec.load("qwen2-0.5b.spill").per_layer()}
    assert not spill & set(NEW)


def test_needed_flops_count_the_head_once_a_call():
    cell = spec.load(CELL)
    fam, conf = cell.family, cell.conf
    assert work.head_flops(fam, conf) == HEAD
    assert work.needed_flops(fam, conf, 0, 2048) == _prefill_flops(2048)
    assert work.needed_flops(fam, conf, 700, 1) == _decode_flops(700) \
        == fam.token_flops(conf, 701)


def test_prefill_roofline_by_hand():
    cell, ctx = _ctx(CALLS, MODULES)
    least = sum(max(_prefill_flops(n) / 197e12, _weights_and_kv(n) / 819e9)
                for n in (2048, 512))
    got = cell.reader("prefill_roofline")(ctx)
    assert got == pytest.approx(least / 0.095 * 100)
    assert 0 < got < 100


def test_decode_roofline_is_the_decode_step_roofline():
    cell, ctx = _ctx(CALLS, MODULES)
    least = [max(_decode_flops(p) / 197e12, _weights_and_kv(p + 1) / 819e9)
             for p in (2048, 512)]
    got = cell.reader("decode_roofline")(ctx)
    assert got == pytest.approx(sum(least) / 2 / 5.45e-3 * 100)
    assert got == cell.reader("decode_step_roofline")(ctx)


def test_model_mfu_by_hand():
    cell, ctx = _ctx(CALLS, MODULES, window_s=4.0)
    flops = _prefill_flops(2048) + _prefill_flops(512) \
        + _decode_flops(2048) + _decode_flops(512)
    assert cell.reader("model_mfu")(ctx) == \
        pytest.approx(flops / (4.0 * 197e12) * 100)


@pytest.mark.parametrize("calls,modules", [([], []), (CALLS, []), ([], MODULES),
                                           (CALLS[2:], MODULES)])
def test_nothing_to_read_reads_none(calls, modules):
    cell, ctx = _ctx(calls, modules)
    got = {n: cell.reader(n)(ctx) for n in NEW}
    if not calls:
        assert got == dict.fromkeys(NEW)
    elif not modules:
        assert got["prefill_roofline"] is None
        assert got["decode_roofline"] is None
        assert got["model_mfu"] > 0
    else:                                  # decode calls alone
        assert got["prefill_roofline"] is None
        assert got["decode_roofline"] > 0
