"""The configuration files hold the published widths, and
BENCHMARK.json names only what exists under its paths."""

import json
import re

import pytest
from servebench_fixtures import BENCH, REPO
from servebench import spec
from servebench.families import dense

QWEN = dict(L=24, d=896, H=14, KV=2, hd=64, f=4864, V=151936, tied=True,
            bias=True, theta=1e6, eps=1e-6)
MISTRAL = dict(L=8, d=4096, H=32, KV=8, hd=128, f=14336, V=32768, tied=False,
               bias=False, theta=1e6, eps=1e-6)


def _conf(name):
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


@pytest.mark.parametrize("name,want", [("qwen2-0.5b", QWEN),
                                       ("mistral-7b-v0.3", MISTRAL)])
def test_published_widths(name, want):
    assert dense.dims(_conf(name)) == want


def test_only_depth_and_the_run_eps_depart():
    q, m = _conf("qwen2-0.5b"), _conf("mistral-7b-v0.3")
    assert q["reduced"] == []
    assert m["reduced"] == ["num_hidden_layers", "rms_norm_eps"]
    assert m["published"] == {"num_hidden_layers": 32, "rms_norm_eps": 1e-5}
    assert m["config"]["sliding_window"] is None


@pytest.mark.parametrize("name", ["qwen2-0.5b", "mistral-7b-v0.3"])
def test_program_config_matches(name):
    c = _conf(name)
    a, w = dense.arch_config(c), dense.dims(c)
    assert (a.n_layers, a.d_model, a.n_heads, a.n_kv_heads, a.hd, a.d_ff,
            a.vocab) == (w["L"], w["d"], w["H"], w["KV"], w["hd"], w["f"], w["V"])
    assert (a.qkv_bias, a.tie_embeddings, a.rope_theta) == \
        (w["bias"], w["tied"], w["theta"])


NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_benchmark_json_resolves():
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    for p in bench["paths"]:
        assert (REPO / p).is_dir()
    metrics = bench["end_to_end"] + bench["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    assert {m["name"] for m in bench["end_to_end"]} == \
        {"tok_s", "ttft_p50_s", "itl_p99_s", "setup_s"}
    for m in bench["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
    for m in bench["per_layer"]:
        assert NAME.match(m["name"])
        assert callable(spec.load(bench["workloads"][0]["name"]).reader(m["name"]))
        assert m["moves"] in {e["name"] for e in bench["end_to_end"]}
    for c in bench["configs"]:
        assert c["reduced"] == _conf(c["name"])["reduced"]
    for w in bench["workloads"]:
        cell = spec.load(w["name"])
        assert NAME.match(w["name"]) and cell.chips == 1
        assert cell.conf["name"] == w["config"]
        assert "serve" in cell.mix and cell.mix["clients"] >= 1
        assert cell.per_layer() and cell.end_to_end()
