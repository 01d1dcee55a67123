"""Operation and byte counts of the dense family against hand counts."""

import json

import pytest
from servebench_fixtures import BENCH
from servebench.families import dense


def _conf(name):
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


def test_qwen2_counts_by_hand():
    c = _conf("qwen2-0.5b")
    attn = 896 * 896 + 2 * 896 * 128 + 896 * 896          # q, k, v, o
    per_layer = attn + (896 + 2 * 128) + 3 * 896 * 4864 + 2 * 896
    assert dense.layer_params(c) == per_layer == 14_912_384
    assert dense.param_count(c) == 151936 * 896 + 24 * per_layer + 896 \
        == 494_032_768                                    # Qwen2-0.5B: 0.49 B
    assert dense.matmul_params(c) == 24 * (attn + 3 * 896 * 4864) + 151936 * 896
    assert dense.kv_bytes_per_token(c) == 24 * 2 * 2 * 64 * 2 == 12288
    assert dense.token_flops(c, 100) == \
        2 * dense.matmul_params(c) + 4 * 24 * 14 * 64 * 100
    assert dense.decode_bytes(c, 300) == 2 * 494_032_768 + 12288 * 300


def test_mistral_counts_by_hand():
    c = _conf("mistral-7b-v0.3")
    per_layer = 2 * 4096 * 4096 + 2 * 4096 * 1024 + 3 * 4096 * 14336 + 2 * 4096
    assert dense.layer_params(c) == per_layer == 218_112_000
    assert dense.param_count(c) == 2 * 32768 * 4096 + 8 * per_layer + 4096 \
        == 2_013_335_552                                   # one 8-layer stage
    whole = json.loads(json.dumps(c))
    whole["config"]["num_hidden_layers"] = 32
    assert dense.param_count(whole) == 7_248_023_552       # Mistral-7B: 7.25 B
    assert dense.kv_bytes_per_token(c) == 8 * 2 * 8 * 128 * 2 == 32768


@pytest.mark.parametrize("name", ["qwen2-0.5b", "mistral-7b-v0.3"])
def test_span_flops_sums_token_flops(name):
    c = _conf(name)
    assert dense.span_flops(c, 0, 1) == dense.token_flops(c, 1)
    assert dense.span_flops(c, 40, 7) == sum(
        dense.token_flops(c, p + 1) for p in range(40, 47))


def test_hlo_bytes_of_a_recorded_kernel_signature():
    from servebench.tracefile import hlo_bytes

    sig = ("%pack_planes_pallas.1 = u8[16,128,128]{2,1,0:T(8,128)(4,1)} "
           "custom-call(u16[8,128,128]{2,1,0:T(8,128)(2,1)S(1)} %bitcast.3), "
           "custom_call_target=\"tpu_custom_call\", "
           "operand_layout_constraints={u16[8,128,128]{2,1,0}}")
    assert hlo_bytes(sig) == 16 * 128 * 128 + 8 * 128 * 128 * 2


def test_least_time_takes_the_larger_bound():
    from servebench import peaks

    kind = "TPU v5 lite"
    assert peaks.least_time_s(197e12, 0, kind) == pytest.approx(1.0)
    assert peaks.least_time_s(0, 819e9, kind) == pytest.approx(1.0)
    assert peaks.least_time_s(197e12, 2 * 819e9, kind) == pytest.approx(2.0)
    with pytest.raises(KeyError):
        peaks.peak("TPU v9 imaginary")
