"""``chip_smoke.py`` on the CPU: its phases at the smoke config, so the
script cannot rot between chip runs, and its refusal to run without a
TPU.  Pallas kernels run in interpret mode here."""

import subprocess
import sys

import numpy as np
import pytest

import chip_smoke as cs
from repro import obs
from repro.kernels import dispatch


@pytest.fixture(scope="module")
def smoke():
    return cs.model(smoke=True, seed=0)


def test_parity_phase(smoke):
    cfg, params = smoke
    pages = cs.kv_pages(cfg, params, prompt_len=64, n_tokens=8, batch=2,
                        seed=0)
    assert len(pages) == cfg.n_layers * 64 // cs.PAGE_TOKENS
    cs.phase_parity(pages)


def test_serve_phase_spills_and_agrees():
    assert cs.phase_serve(True, prompt_len=64, n_tokens=8, batch=2,
                          seed=0) > 0


def test_schedule_phase_retires_everything():
    cs.phase_schedule(True, num_requests=3, prompt_len=32, n_tokens=4,
                      batch=1, seed=0)


def test_device_path_guard():
    counters = {f"kernel.{k}.{dispatch.DEVICE}": 2 for k in cs.KERNELS}
    cs._check_device_paths(dict(counters, **{"sched.tokens": 9}))
    with pytest.raises(AssertionError, match="off the device path"):
        cs._check_device_paths(dict(counters, **{"kernel.lz4.numpy": 1}))
    with pytest.raises(AssertionError, match="no device call"):
        cs._check_device_paths({"kernel.pack.device": 1})


def test_tier_takes_numpy_twins_off_the_tpu():
    obs.reset()
    cs.phase_serve(True, prompt_len=32, n_tokens=2, batch=1, seed=1)
    counters = obs.snapshot()["counters"]
    assert counters[f"kernel.pack.{dispatch.NUMPY}"] > 0
    assert not any(k.endswith(f".{dispatch.DEVICE}") for k in counters)


def test_main_refuses_cpu(capsys):
    assert cs.main([]) != 0
    out, err = capsys.readouterr()
    assert "'cpu'" in err and '"ok"' not in out


_CACHE_PROBE = """
import sys
import jax, jax.numpy as jnp
import repro.compile_cache as cc
cc.DEFAULT_DIR = cc.Path(sys.argv[1])
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
print(cc.enable_compile_cache())
jax.jit(lambda x: x * 2 + 1)(jnp.arange(8.0)).block_until_ready()
"""


@pytest.mark.parametrize("from_env", [True, False])
def test_compile_cache_goes_where_it_is_told(tmp_path, monkeypatch,
                                             from_env):
    env_dir, default_dir = tmp_path / "env", tmp_path / "default"
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    if from_env:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(env_dir))
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    out = subprocess.run(
        [sys.executable, "-c", _CACHE_PROBE, str(default_dir)],
        capture_output=True, text=True, timeout=120, check=True).stdout
    used, unused = ((env_dir, default_dir) if from_env
                    else (default_dir, env_dir))
    assert out.split()[-1] == str(used)
    assert any(used.iterdir()) and not unused.exists()


def test_kv_pages_are_the_engine_pages(smoke):
    """The parity phase's pages have the serving engine's page shape."""
    cfg, params = smoke
    pages = cs.kv_pages(cfg, params, prompt_len=32, n_tokens=0, batch=2,
                        seed=0)
    channels = cfg.n_kv_heads * cfg.head_dim
    assert all(p.shape == (cs.PAGE_TOKENS * 2, channels)
               and p.dtype == np.uint16 for p in pages)
