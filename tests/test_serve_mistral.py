"""A Mistral-shaped model served through ``ServeScheduler`` agrees with
the plain float32 reference of the serving benchmark
(``benchmarks/serve/servebench/families/dense.py`` ``reference_logits``).

The shape is Mistral-7B-v0.3's at a small width: GQA 4:1, no QKV bias, an
untied head, ``rope_theta`` 1e6 and the published ``rms_norm_eps`` 1e-5,
on seeded random bf16 weights.  Two requests are served together, prefill
then decode through ``KVPagePool``: one of 512 prompt tokens, and one of
1280, which is past 1024 and a multiple of 256, so its prefill attention
scans query chunks (``layers.query_chunk``).  Every logits row the engine
returned (the prefill's last row, then each decode step's) is compared
with the reference's full forward pass over the prompt and the served
tokens.
"""

import dataclasses
import json
import sys
from pathlib import Path

import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[1]
BENCH = REPO / "benchmarks" / "serve"
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

from servebench.families import dense  # noqa: E402

from repro import obs  # noqa: E402
from repro.core.tier import make_device  # noqa: E402
from repro.models import layers  # noqa: E402
from repro.runtime import paging, serving  # noqa: E402

PROMPTS = (512, 1280)
NEW_TOKENS = 8
SEED = 20261018

#: Root mean square of served minus reference logits.  The logits have
#: unit spread; the served path holds activations in bf16 (8 significant
#: bits) against the reference's float32, which reads ~0.009 here.  The
#: float8 control (3 significant bits) reads ~0.14.
RMSE = 0.03
#: Largest single difference: bf16 reads ~0.04, the control ~0.6.
MAX_ABS = 0.15


def _conf(eps=1e-5):
    conf = json.loads((BENCH / "configs" / "mistral-7b-v0.3.json").read_text())
    conf["config"].update(hidden_size=128, intermediate_size=256,
                          num_attention_heads=8, num_key_value_heads=2,
                          num_hidden_layers=2, vocab_size=512,
                          rms_norm_eps=eps)
    return conf


def _serve(acfg, params, prompts, n_new, monkeypatch):
    """Serve ``prompts`` together; per request, its prompt, its tokens and
    every logits row its engine returned, in order."""
    rows = {}
    prefill, decode = serving.ServeEngine.prefill, serving.ServeEngine.decode

    def keep(fn):
        def call(self, tokens):
            out = fn(self, tokens)
            rows.setdefault(self.tag, []).append(np.asarray(out, np.float32))
            return out
        return call

    monkeypatch.setattr(serving.ServeEngine, "prefill", keep(prefill))
    monkeypatch.setattr(serving.ServeEngine, "decode", keep(decode))
    sched = serving.ServeScheduler(
        acfg, params, max_batch=len(prompts),
        device_kind=make_device("trace", shards=1),
        policy=paging.LOSSLESS_POLICY, batch=1, page_tokens=16,
        hbm_kv_budget=1 << 30)
    sched.submit([serving.ServeRequest(req_id=i, arrival=0, prompt=p,
                                       max_new_tokens=n_new)
                  for i, p in enumerate(prompts)])
    sched.run()
    return [(p.reshape(-1), sched.records[i].tokens.reshape(-1),
             np.concatenate(rows[f"r{i}"]))
            for i, p in enumerate(prompts)]


def _reference(conf, params, prompt, tokens, low=False):
    """The reference's logits at the positions that picked each token."""
    seq = np.concatenate([prompt, tokens[:-1]])
    at = np.arange(prompt.size - 1, seq.size)
    return dense.reference_logits(conf, params, seq, at, low=low)


def _rmse(a, b):
    return float(np.sqrt(np.mean(np.square(a - b))))


@pytest.fixture(scope="module")
def served():
    conf = _conf()
    acfg = dataclasses.replace(dense.arch_config(conf), norm_eps=1e-5)
    params = dense.make_params(acfg, SEED)
    rng = np.random.default_rng(SEED)
    prompts = [rng.integers(0, 512, (1, n), dtype=np.int32) for n in PROMPTS]
    obs.reset()
    with pytest.MonkeyPatch.context() as mp:
        out = _serve(acfg, params, prompts, NEW_TOKENS, mp)
    counters = obs.snapshot()["counters"]
    obs.reset()
    return conf, params, out, counters


def test_the_shape_is_mistral(served):
    conf = served[0]
    a = dataclasses.replace(dense.arch_config(conf), norm_eps=1e-5)
    assert (a.n_heads // a.n_kv_heads, a.qkv_bias, a.tie_embeddings) == (4, False, False)
    assert (a.rope_theta, dense.dims(conf)["eps"]) == (1e6, 1e-5)


@pytest.mark.parametrize("i", range(len(PROMPTS)), ids=[f"prompt{n}" for n in PROMPTS])
def test_served_logits_match_the_reference(served, i):
    conf, params, out, _ = served
    prompt, tokens, prog = out[i]
    assert prog.shape == (NEW_TOKENS, 512)
    ref = _reference(conf, params, prompt, tokens)
    assert _rmse(prog, ref) <= RMSE
    assert np.abs(prog - ref).max() <= MAX_ABS


@pytest.mark.parametrize("i", range(len(PROMPTS)), ids=[f"prompt{n}" for n in PROMPTS])
def test_the_float8_control_fails_the_tolerance(served, i):
    conf, params, out, _ = served
    prompt, tokens, _ = out[i]
    ref = _reference(conf, params, prompt, tokens)
    low = _reference(conf, params, prompt, tokens, low=True)
    assert _rmse(low, ref) > RMSE
    assert np.abs(low - ref).max() > MAX_ABS


def test_only_the_long_prompt_scans_query_chunks(served):
    assert [layers.query_chunk(n) for n in PROMPTS] == [0, 256]
    assert served[3]["model.prefill_chunked"] == 1


def test_the_published_eps_reaches_every_norm(monkeypatch):
    """With embeddings a thousandth of their scale, the first norm's
    input has a mean square near 1e-8, so its epsilon sets the result:
    the served path agrees with the reference at 1e-5 and not at 1e-6."""
    acfg = dataclasses.replace(dense.arch_config(_conf()), norm_eps=1e-5)
    params = dict(dense.make_params(acfg, SEED))
    params["embed"] = (params["embed"].astype(np.float32) * 1e-3).astype(
        params["embed"].dtype)
    prompt = np.random.default_rng(SEED).integers(0, 512, (1, 64), dtype=np.int32)
    (p, tokens, prog), = _serve(acfg, params, [prompt], 4, monkeypatch)
    assert _rmse(prog, _reference(_conf(1e-5), params, p, tokens)) <= RMSE
    assert _rmse(prog, _reference(_conf(1e-6), params, p, tokens)) > 10 * RMSE
