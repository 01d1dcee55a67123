"""The closed-loop traffic generator: fixed sizes, seeded order."""

import collections
import json

import numpy as np
from servebench_fixtures import BENCH
from servebench.traffic import Traffic, length_table


def test_value_table_has_exact_shares():
    t = length_table({"values": [512, 1024, 2048], "weights": [0.4, 0.4, 0.2]}, 40)
    assert collections.Counter(t.tolist()) == {512: 16, 1024: 16, 2048: 8}


def test_lognormal_table_is_clipped_and_centred():
    spec = {"lognormal_median": 64, "sigma": 1.0, "min": 16, "max": 256}
    t = length_table(spec, 40)
    assert t.size == 40 and t.min() == 16 and t.max() == 256
    assert np.all(np.diff(t) >= 0)
    assert 60 <= np.median(t) <= 68


def _mix(name):
    return json.loads((BENCH / "traffic" / f"{name}.json").read_text())


def _stream(seed, n=45, client=3):
    loop = Traffic(_mix("chat"), 32768, seed, 16)
    return [loop.next(client) for _ in range(n)]


def test_same_seed_same_requests():
    a, b = _stream(2**33 + 5), _stream(2**33 + 5)
    assert all(np.array_equal(x.prompt, y.prompt) and x.n_out == y.n_out
               for x, y in zip(a, b))


def _round(seed, mix="chat"):
    t = Traffic(_mix(mix), 32768, seed, 16)
    return [[t.next(c) for _ in range(5)] for c in range(t.clients)]


def test_each_round_deals_the_whole_table():
    for mix in ("chat", "spill"):
        t = Traffic(_mix(mix), 32768, 0, 16)
        for seed in (11, 12):
            reqs = [r for row in _round(seed, mix) for r in row]
            assert sorted(r.n_out for r in reqs) == sorted(t.outputs.tolist())
            assert sorted(r.prompt.shape[1] for r in reqs) == \
                sorted(t.prompts.tolist())


def test_every_client_gets_one_length_of_each_stratum():
    t = Traffic(_mix("spill"), 32768, 0, 16)
    strata = t.outputs.reshape(-1, t.clients)
    for row in _round(2**33 + 7, "spill"):
        got = sorted(r.n_out for r in row)
        assert all(lo <= v <= hi for v, lo, hi in
                   zip(got, strata.min(1), strata.max(1)))


def test_other_seed_other_order():
    a, b = _round(11), _round(12)
    assert [r.n_out for row in a for r in row] != [r.n_out for row in b for r in row]
    assert any(not np.array_equal(x.prompt[0, :8], y.prompt[0, :8])
               for x, y in zip(a[0], b[0]))


def test_preroll_covers_every_prompt_length_and_staggers():
    mix = _mix("chat")
    loop = Traffic(mix, 32768, 1, 16)
    pre = [loop.preroll(c) for c in range(loop.clients)]
    assert {p.prompt.shape[1] for p in pre} == {512, 1024, 2048}
    assert [p.n_out for p in pre] == [17 + 2 * c for c in range(8)]
    assert loop.max_prompt == 2048 and loop.max_output == 256


def test_prompt_ids_in_vocab():
    for r in _stream(7, 10):
        assert r.prompt.dtype == np.int32
        assert 0 <= r.prompt.min() and r.prompt.max() < 32768
