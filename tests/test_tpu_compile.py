"""The served path's kernels compile for a TPU v5e chip.

Interpret mode cannot see what the chip's compiler refuses (a reshape
that splits the lane dim, an unsigned compare, a block shape off the
(8, 128) tiling), so these tests ahead-of-time compile each main-path
kernel, at the shapes the tier really produces, for a *described* v5e
chip — no chip is attached, nothing runs.  Plus the full-width
qwen2-0.5b decode step, the program that drives them.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.experimental.compilation_cache import compilation_cache
from jax.sharding import SingleDeviceSharding

from repro.configs import ARCHS
from repro.core.bitplane import BF16_BITS, BLOCK_ELEMS
from repro.core.tier import BitplaneLayout
from repro.kernels import bitplane as kb
from repro.kernels import dispatch
from repro.kernels import lz4 as kl
from repro.kernels import pnm_score as kp

QWEN = ARCHS["qwen2-0.5b"]
KV_CHANNELS = QWEN.n_kv_heads * (QWEN.d_model // QWEN.n_heads)   # 2 x 64
PAGE_ROWS = 16 * 2                       # 16-token page at batch 2


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip cannot be read back without one:
    keep such entries out of any persistent cache."""
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


@pytest.fixture
def compile_for(one_chip, no_persistent_cache):
    def shape(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def run(fn, *shapes, **static):
        return fn.lower(*(shape(*s) for s in shapes), **static).compile()

    return run


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("elems", [8 * 1024, BitplaneLayout.ENCODE_SLAB_ELEMS])
def test_pack_planes_compiles_for_v5e(compile_for, elems):
    """The encode slab as ``pack_planes_slab`` lays it out on a TPU."""
    rows = elems // kb._PACK_C
    compiled = compile_for(kb.pack_planes_pallas,
                           ((rows, kb._PACK_C), jnp.uint16),
                           block_r=min(rows, kb.DEFAULT_BLOCK_R),
                           interpret=False)
    _assert_kernel(compiled)


@pytest.mark.parametrize("windows", [64, 32])
def test_kv_pack_program_compiles_for_v5e(compile_for, windows):
    """The KV transform and pack program over a slab of qwen2-0.5b page
    windows (16 tokens x 128 channels): 64 is an admission's slab and a
    batch-1 decode boundary's 48 windows padded; 32 the rest of a
    batch-2 decode boundary's 96.  The pack kernel writes its planes to
    HBM, as it does alone (no ``S(1)`` on its result)."""
    compiled = compile_for(kb._kv_pack_program,
                           ((windows, 16, KV_CHANNELS), jnp.uint16),
                           interpret=False)
    _assert_kernel(compiled)
    pack, = [line for line in compiled.as_text().splitlines()
             if "%pack_planes_pallas" in line and "custom-call(" in line]
    assert "S(1)" not in pack.split("custom-call(")[0]


def test_lz4_match_pipeline_compiles_for_v5e(compile_for):
    """The whole jitted match pipeline over one packed 128K-element
    slab: 16 planes x 64 blocks of ``BLOCK_ELEMS // 8``-byte streams."""
    n_bytes = BitplaneLayout.ENCODE_SLAB_ELEMS * BF16_BITS // 8
    L = BLOCK_ELEMS // 8
    S = n_bytes // L
    row_start_end = S * (L // kl.MIN_MATCH + 1)
    compiled = compile_for(
        kl._device_match, ((n_bytes,), jnp.uint8), ((S,), jnp.int32),
        ((S,), jnp.int32), ((S,), jnp.int32),
        L=L, E=dispatch.bucket(row_start_end), interpret=False)
    _assert_kernel(compiled)


@pytest.mark.parametrize("pages", [8, 512])
def test_page_scores_compile_for_v5e(compile_for, pages):
    """qwen2-0.5b pages: 16 tokens x batch 2 rows of 2 x 64 channels."""
    compiled = compile_for(
        kp.page_scores_pallas,
        ((pages, PAGE_ROWS, KV_CHANNELS), jnp.float32),
        ((pages,), jnp.int32), ((KV_CHANNELS,), jnp.float32),
        interpret=False)
    _assert_kernel(compiled)


def test_qwen2_full_width_decode_step_compiles_for_v5e(one_chip,
                                                      no_persistent_cache):
    """The served model step at published widths fits one v5e chip."""
    from repro.models import init_cache, init_params
    from repro.runtime.serving import _jit_step

    def on_chip(tree):
        return jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                           sharding=one_chip), tree)

    batch, max_seq = 2, 1024
    params = on_chip(jax.eval_shape(
        lambda: init_params(QWEN, jax.random.PRNGKey(0))))
    cache = on_chip(jax.eval_shape(lambda: init_cache(QWEN, batch, max_seq)))
    step = on_chip({"tokens": jax.ShapeDtypeStruct((batch, 1), jnp.int32),
                    "cache_pos": jax.ShapeDtypeStruct((), jnp.int32)})
    compiled = _jit_step.lower(QWEN, params, step, cache).compile()
    assert compiled.memory_analysis().argument_size_in_bytes < 16e9


def test_qwen2_full_width_batched_decode_step_compiles_for_v5e(
        one_chip, no_persistent_cache):
    """The scheduler's batched step at published widths: eight slot rows,
    each at its own position, and the slot cache's in-place row write."""
    from repro.models import init_cache, init_params
    from repro.runtime.serving import _jit_step, _write_rows_jit

    def on_chip(tree):
        return jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                           sharding=one_chip), tree)

    rows, max_seq = 8, 528
    params = on_chip(jax.eval_shape(
        lambda: init_params(QWEN, jax.random.PRNGKey(0))))
    cache = on_chip(jax.eval_shape(lambda: init_cache(QWEN, rows, max_seq)))
    step = on_chip({"tokens": jax.ShapeDtypeStruct((rows, 1), jnp.int32),
                    "cache_pos": jax.ShapeDtypeStruct((rows,), jnp.int32)})
    compiled = _jit_step.lower(QWEN, params, step, cache).compile()
    assert compiled.memory_analysis().argument_size_in_bytes < 16e9
    full = cache["layers"]["k"]
    one = on_chip(jax.ShapeDtypeStruct((full.shape[0], 1) + full.shape[2:],
                                       full.dtype))
    _write_rows_jit.lower(full, one, on_chip(
        jax.ShapeDtypeStruct((), jnp.int32))).compile()
