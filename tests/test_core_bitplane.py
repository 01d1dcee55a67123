"""Lossless round-trip properties of the TRACE core transforms."""

import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # hypothesis optional: only @given tests skip
    class _AnyStrategy:
        """Chainable stand-in so module-level strategy expressions parse."""

        def __call__(self, *a, **k):
            return self

        def __getattr__(self, name):
            return self

    st = _AnyStrategy()

    def given(*a, **k):
        def deco(fn):
            @pytest.mark.skip(reason="hypothesis not installed")
            def skipped():
                pass

            skipped.__name__ = fn.__name__
            return skipped

        return deco

    def settings(*a, **k):
        return lambda fn: fn

import encode_ref
from repro.core import bitplane as bp
from repro.core import codec
from repro.core import kv_transform as kvt
from repro.core import precision as prec


u16_arrays = st.integers(0, 2**16 - 1)


@given(st.lists(u16_arrays, min_size=8, max_size=512).filter(lambda l: len(l) % 8 == 0))
@settings(max_examples=50, deadline=None)
def test_pack_unpack_roundtrip(vals):
    x = np.array(vals, dtype=np.uint16)
    planes = bp.pack_planes(x)
    assert planes.shape == (16, len(vals) // 8)
    y = bp.unpack_planes(planes, len(vals))
    np.testing.assert_array_equal(x, y)


def test_pack_unpack_jnp_matches_numpy():
    rng = np.random.default_rng(0)
    x = rng.integers(0, 2**16, size=256, dtype=np.uint16)
    pn = bp.pack_planes(x)
    pj = np.asarray(bp.pack_planes_jnp(x))
    np.testing.assert_array_equal(pn, pj)
    yj = np.asarray(bp.unpack_planes_jnp(pj, 256))
    np.testing.assert_array_equal(x, yj)


def test_special_values_roundtrip():
    import ml_dtypes

    specials = np.array(
        [0x7F80, 0xFF80, 0x7FC0, 0x7FFF, 0x0001, 0x8000, 0x0000],  # inf,-inf,nan,nan,subnormal,-0,0
        dtype=np.uint16,
    )
    x = np.tile(specials, 8)[:56]
    x = np.pad(x, (0, 8 - x.size % 8))
    y = bp.unpack_planes(bp.pack_planes(x), x.size)
    np.testing.assert_array_equal(x, y)
    _ = x.view(ml_dtypes.bfloat16)  # merely checks the view is legal


@given(
    st.integers(1, 16),   # tokens (rows)
    st.integers(1, 32),   # channels
    st.integers(0, 2**32 - 1),
)
@settings(max_examples=40, deadline=None)
def test_kv_transform_roundtrip(n, c, seed):
    rng = np.random.default_rng(seed)
    block = rng.integers(0, 2**16, size=(n, c), dtype=np.uint16)
    stream, meta = kvt.kv_forward(block)
    back = kvt.kv_inverse(stream, meta)
    np.testing.assert_array_equal(block, back)


def test_kv_pack_unpack_roundtrip():
    rng = np.random.default_rng(1)
    block = rng.integers(0, 2**16, size=(64, 32), dtype=np.uint16)
    planes, meta = kvt.kv_pack(block)
    back = kvt.kv_unpack(planes, meta)
    np.testing.assert_array_equal(block, back)


def test_kv_transform_reduces_exponent_entropy():
    """Smooth per-channel series must yield near-empty high delta planes."""
    import ml_dtypes

    rng = np.random.default_rng(2)
    base = rng.normal(0, 1, size=(1, 64))
    walk = base + 0.01 * np.cumsum(rng.normal(0, 1, size=(128, 64)), axis=0)
    block = walk.astype(ml_dtypes.bfloat16).view(np.uint16)
    stream, _ = kvt.kv_forward(block)
    planes = bp.pack_planes(stream)
    # top 4 delta-exponent planes (bits 14..11) should be mostly zero bytes
    top = planes[11:15]
    assert (top == 0).mean() > 0.9


def test_kv_forward_jnp_matches_numpy():
    rng = np.random.default_rng(3)
    block = rng.integers(0, 2**16, size=(32, 16), dtype=np.uint16)
    stream, meta = kvt.kv_forward(block)
    out_j = np.asarray(kvt.kv_forward_jnp(block, meta.beta)).ravel()
    np.testing.assert_array_equal(stream, out_j)


# ---------------------------------------------------------------------------
# precision views
# ---------------------------------------------------------------------------

def test_full_view_is_identity():
    rng = np.random.default_rng(4)
    x = rng.integers(0, 2**16, size=2048, dtype=np.uint16)
    planes = bp.pack_planes(x)
    y = prec.assemble_from_planes(planes, x.size, prec.FULL)
    np.testing.assert_array_equal(x, y)


@pytest.mark.parametrize("view", [prec.MAN4, prec.MAN2, prec.MAN0])
def test_view_matches_truncation_oracle(view):
    rng = np.random.default_rng(5)
    x = rng.integers(0, 2**16, size=2048, dtype=np.uint16)
    planes = bp.pack_planes(x)
    got = prec.assemble_from_planes(planes, x.size, view)
    want = prec.truncate_reference(x, view)
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("view", [prec.MAN4, prec.MAN2])
def test_guard_rounding_beats_truncation(view):
    """RNE with guard planes must have ≤ error of plain truncation."""
    import ml_dtypes

    rng = np.random.default_rng(6)
    f = rng.normal(0, 1, size=4096).astype(ml_dtypes.bfloat16)
    x = f.view(np.uint16)
    planes = bp.pack_planes(x)
    rounded = prec.assemble_from_planes(planes, x.size, view)
    trunc_view = prec.PrecisionView(r_e=view.r_e, r_m=view.r_m, d_m=0)
    truncated = prec.assemble_from_planes(planes, x.size, trunc_view)
    err_r = np.abs(
        rounded.view(ml_dtypes.bfloat16).astype(np.float64) - f.astype(np.float64)
    ).mean()
    err_t = np.abs(
        truncated.view(ml_dtypes.bfloat16).astype(np.float64) - f.astype(np.float64)
    ).mean()
    assert err_r <= err_t * 1.0001
    assert np.isfinite(err_r)


def test_view_plane_counts():
    assert prec.FULL.bits == 16
    assert len(prec.FULL.fetched_planes()) == 16
    assert prec.MAN0.bits == 9
    assert len(prec.MAN0.fetched_planes()) == 10  # + 1 guard plane
    assert prec.MAN2.plane_mask() & (1 << 15)


def test_qnan_preserved_under_views():
    """Quiet NaNs (mantissa MSB set — all NaNs produced by IEEE hardware)
    survive any view with r_m >= 1.  A *signaling* NaN whose payload lives
    only in dropped planes is physically unreadable by plane-aligned fetch
    and collapses to Inf; documented semantics, not a bug."""
    x = np.full(64, 0x7FC1, dtype=np.uint16)  # qNaN + low payload bit
    planes = bp.pack_planes(x)
    y = prec.assemble_from_planes(planes, 64, prec.MAN2)
    exp_mask, man_mask = 0x7F80, 0x007F
    assert ((y & exp_mask) == exp_mask).all()
    assert ((y & man_mask) != 0).all()  # still NaN, not Inf
    inf = np.full(8, 0xFF80, dtype=np.uint16)  # -Inf survives exactly
    yi = prec.assemble_from_planes(bp.pack_planes(inf), 8, prec.MAN0)
    np.testing.assert_array_equal(yi, inf)


# ---------------------------------------------------------------------------
# codecs
# ---------------------------------------------------------------------------

@given(st.binary(min_size=0, max_size=4096))
@settings(max_examples=60, deadline=None)
def test_lz4_roundtrip(data):
    comp = encode_ref.lz4_compress(data)
    back = codec.lz4_decompress(comp)
    assert back == data


def test_lz4_compresses_runs():
    data = b"\x00" * 4096
    comp = encode_ref.lz4_compress(data)
    assert len(comp) < 64
    assert codec.lz4_decompress(comp) == data


@pytest.mark.skipif(not codec.HAVE_ZSTD, reason="zstandard not installed")
def test_zstd_roundtrip():
    rng = np.random.default_rng(7)
    data = rng.integers(0, 4, size=4096, dtype=np.uint8).tobytes()
    comp = codec.zstd_compress(data)
    assert codec.zstd_decompress(comp, max_out=4096) == data


def test_bypass_on_incompressible():
    rng = np.random.default_rng(8)
    data = rng.integers(0, 256, size=4096, dtype=np.uint8).tobytes()
    (payload,), (flag,) = codec.compress_slab(*encode_ref.slab_of([data]),
                                              "lz4")
    assert (payload, flag) == encode_ref.compress_block(data, "lz4")
    if flag == codec.RAW:
        assert payload == data
    assert codec.decompress_block(payload, flag, "lz4", len(data)) == data
