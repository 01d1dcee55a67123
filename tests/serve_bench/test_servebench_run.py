"""``run.py`` refuses to run without a TPU; below that check, a run of a
throwaway cell added as files only goes through on the CPU."""

import json
import os
import subprocess
import sys
import time

import jax
import numpy as np
from servebench_fixtures import BENCH, REPO, TINY


def test_run_py_exits_nonzero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload", "qwen2-0.5b.spill",
         "--seed", "5", "--seconds", "1", "--trace", "0"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr


def test_throwaway_cell_runs_from_files_alone(tiny_root):
    from servebench import cell, spec

    c = spec.load(TINY, tiny_root)
    assert [m["name"] for m in c.per_layer()][-1] == "ticks_per_s"
    out = cell.run(c, 2**33 + 1, 1.5, False, time.perf_counter())
    assert out["correct"] is True
    assert set(out["metrics"]) == {"tok_s", "ttft_p50_s", "itl_p99_s", "setup_s"}
    assert all(v["value"] > 0 for v in out["metrics"].values())
    assert out["attempted"] > 0 and out["failed"] == 0
    assert list(out)[-1] == "check"
    assert out["check"]["logit_rmse"]["value"] <= 0.02
    json.dumps(out)


def test_new_metric_reader_is_found_by_name(tiny_root):
    from servebench import spec
    from servebench.spans import Spans

    c = spec.load(TINY, tiny_root)
    sp = Spans()
    sp.count["sched.step"] = 30

    class Ctx:
        spans, window_s = sp, 3.0

    assert c.reader("ticks_per_s")(Ctx) == 10.0


def test_weights_follow_the_seed():
    from servebench.families import dense
    from servebench_fixtures import make_root  # noqa: F401

    conf = json.loads((BENCH / "configs" / "qwen2-0.5b.json").read_text())
    conf["config"].update(hidden_size=64, intermediate_size=128,
                          num_attention_heads=4, num_key_value_heads=2,
                          num_hidden_layers=2, vocab_size=512)
    acfg = dense.arch_config(conf)
    a = dense.make_params(acfg, 2**40 + 3)
    b = dense.make_params(acfg, 2**40 + 3)
    c = dense.make_params(acfg, 2**40 + 4)
    la, lb, lc = (jax.tree.leaves(t) for t in (a, b, c))
    assert all(x.dtype == np.dtype("bfloat16") for x in la)
    assert all(np.array_equal(np.asarray(x), np.asarray(y)) for x, y in zip(la, lb))
    assert not np.array_equal(np.asarray(la[0]), np.asarray(lc[0]))
