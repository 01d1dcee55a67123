"""kernels/lz4: the device-resident LZ4 match kernel's correctness story.

Four pillars:

1. **byte identity** — the kernel path (``codec._lz4_slab_streams`` →
   ``kernels.lz4.match_events_slab`` + ``lz4_emit_events``) produces the
   same bytes as the scalar per-block reference
   (``encode_ref.lz4_compress``), on an adversarial corpus and on
   hypothesis-generated batches;
2. **device parity** — the pallas+jnp path (``force="device"``,
   interpret mode on CPU) selects the exact events of the numpy path;
3. **decode hardening** — truncated and bit-flipped frames raise the
   structured :class:`codec.CorruptPayloadError`, never IndexError or a
   silently-wrong payload accepted as valid;
4. **R6 purity** — the pallas kernel body is recognized by tracecheck's
   jit-purity rule and lints host-sync-free (the check is asserted
   non-vacuous: ``_prep_kernel`` must be in the traced-function set).
"""

import os
from pathlib import Path

import numpy as np
import pytest

import encode_ref
from repro.core import codec
from repro.kernels import lz4 as klz4

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:  # pragma: no cover - hypothesis is a pinned CI dep
    HAVE_HYPOTHESIS = False

REPO_ROOT = Path(__file__).resolve().parents[1]
KERNEL_FILE = REPO_ROOT / "src" / "repro" / "kernels" / "lz4.py"


def _adversarial_corpus():
    """The ISSUE's named adversaries plus the boundary cases the match
    rules care about (MFLIMIT edge, run-first anchoring, hash floods)."""
    rng = np.random.default_rng(7)
    return [
        b"\x00" * 4096,                                     # all-zero
        bytes(rng.integers(0, 256, 4096, dtype=np.uint8)),  # incompressible
        b"a" * 500,                                         # offset-1 run
        b"ab" + b"c" * 300 + b"de",                         # run + tails
        b"abcd" * 1024,                                     # stride-4 periodic
        b"",                                                # empty
        b"x",                                               # 1 byte
        b"\x00" * (klz4.MFLIMIT + 1),                       # smallest matchable
        b"\x00" * klz4.MFLIMIT,                             # all-literal edge
        bytes(rng.integers(0, 2, 2048, dtype=np.uint8)),    # low-entropy
        bytes(np.tile(rng.integers(0, 256, 97).astype(np.uint8), 40)),
        (b"\x00" * 64
         + bytes(rng.integers(0, 256, 64, dtype=np.uint8))) * 16,
    ]


def _scalar_oracle(chunks):
    return [encode_ref.lz4_compress(c) for c in chunks]


def _kernel_path(chunks):
    return codec._lz4_slab_streams(*encode_ref.slab_of(chunks))


# ---------------------------------------------------------------------------
# 1. kernel path vs scalar oracle — byte identity
# ---------------------------------------------------------------------------

def test_kernel_batch_identical_to_scalar_on_adversarial_corpus():
    chunks = _adversarial_corpus()
    scalar = _scalar_oracle(chunks)
    assert _kernel_path(chunks) == scalar
    # every frame round-trips under the hardened decoder
    for data, comp in zip(chunks, scalar):
        if data:
            assert codec.lz4_decompress(comp, max_out=len(data)) == data


if HAVE_HYPOTHESIS:
    @given(st.lists(
        st.one_of(
            st.binary(min_size=0, max_size=1024),
            # byte runs and short-period tiles: the offset-1/stride rules
            st.builds(lambda b, n: b * n, st.binary(min_size=1, max_size=4),
                      st.integers(0, 400)),
        ),
        min_size=1, max_size=8))
    @settings(max_examples=40, deadline=None)
    def test_kernel_batch_identical_to_scalar_any_chunks(chunks):
        assert _kernel_path(chunks) == _scalar_oracle(chunks)
else:  # pragma: no cover - hypothesis is a pinned CI dep
    @pytest.mark.skip(reason="property test needs hypothesis")
    def test_kernel_batch_identical_to_scalar_any_chunks():
        pass


def test_match_events_slab_gapped_streams_untouched():
    """Bypassed (gapped) slab ranges must never influence match events:
    compressing streams sliced out of a gapped slab equals compressing
    the same streams from a dense one."""
    rng = np.random.default_rng(3)
    a = b"\x00" * 256
    b = bytes(rng.integers(0, 4, 256, dtype=np.uint8))
    gap = bytes(rng.integers(0, 256, 64, dtype=np.uint8))
    slab = np.frombuffer(a + gap + b, dtype=np.uint8)
    starts, ends = [0, 256 + 64], [256, 256 + 64 + 256]
    pos, dist, mlen = klz4.match_events_slab(slab, starts, ends)
    dense = np.frombuffer(a + b, dtype=np.uint8)
    dpos, ddist, dmlen = klz4.match_events_slab(dense, [0, 256], [256, 512])
    # same events modulo the gap's offset shift on the second stream
    shift = np.where(dpos >= 256, 64, 0)
    np.testing.assert_array_equal(pos, dpos + shift)
    np.testing.assert_array_equal(dist, ddist)
    np.testing.assert_array_equal(mlen, dmlen)


# ---------------------------------------------------------------------------
# 2. device (pallas+jnp) path parity — interpret mode on CPU
# ---------------------------------------------------------------------------

def test_device_path_matches_numpy_path():
    pytest.importorskip("jax", reason="device path needs jax")
    rng = np.random.default_rng(5)
    parts = [
        np.zeros(300, np.uint8),
        rng.integers(0, 256, 300, dtype=np.uint8),
        np.tile(np.arange(4, dtype=np.uint8), 100),
        rng.integers(0, 3, 300, dtype=np.uint8),
    ]
    buf = np.concatenate(parts)
    ends = np.cumsum([p.size for p in parts])
    starts = ends - [p.size for p in parts]
    ref = klz4.match_events_slab(buf, starts, ends, force="numpy")
    dev = klz4.match_events_slab(buf, starts, ends, force="device")
    for r, d in zip(ref, dev):
        np.testing.assert_array_equal(r, d)
    # ... and the full encode built on those events stays byte-identical
    chunks = [p.tobytes() for p in parts]
    assert codec._lz4_slab_streams(buf, starts, ends,
                                   force="device") == _scalar_oracle(chunks)


# ---------------------------------------------------------------------------
# 3. decode hardening — corrupt frames raise structured errors
# ---------------------------------------------------------------------------

def _fuzz_corpus():
    rng = np.random.default_rng(17)
    return [
        b"\x00" * 600,
        b"the quick brown fox " * 40,
        bytes(rng.integers(0, 8, 700, dtype=np.uint8)),
        bytes(rng.integers(0, 256, 300, dtype=np.uint8)),
    ]


def test_decompress_truncated_frames_raise_structured_error():
    """Every proper prefix of a valid frame either raises
    CorruptPayloadError or decodes to a prefix-consistent payload —
    never IndexError, never bytes past the original."""
    for data in _fuzz_corpus():
        comp = encode_ref.lz4_compress(data)
        for cut in range(len(comp)):
            try:
                out = codec.lz4_decompress(comp[:cut], max_out=len(data))
            except codec.CorruptPayloadError:
                continue
            assert data.startswith(out)


def test_decompress_bitflipped_frames_never_crash():
    """Single-bit flips at every byte: each either raises the structured
    error or decodes within the caller's bound — IndexError/OverflowError
    (the pre-hardening failure modes) are regressions."""
    for data in _fuzz_corpus():
        comp = encode_ref.lz4_compress(data)
        stride = max(1, len(comp) // 128)   # cap work on long frames
        for i in range(0, len(comp), stride):
            for bit in (0x01, 0x80):
                bad = bytearray(comp)
                bad[i] ^= bit
                try:
                    out = codec.lz4_decompress(bytes(bad), max_out=len(data))
                except codec.CorruptPayloadError:
                    continue
                assert len(out) <= len(data)


def test_decompress_rejects_zero_and_early_offsets():
    # offset 0: token 0x04 (0 literals, 4-byte match), offset bytes 00 00
    with pytest.raises(codec.CorruptPayloadError):
        codec.lz4_decompress(b"\x04\x00\x00")
    # offset beyond the produced frontier (1 literal, offset 5)
    with pytest.raises(codec.CorruptPayloadError):
        codec.lz4_decompress(b"\x14A\x05\x00")
    assert issubclass(codec.CorruptPayloadError, ValueError)


# ---------------------------------------------------------------------------
# 4. tracecheck R6 — the kernel body stays host-sync-free
# ---------------------------------------------------------------------------

def test_r6_covers_and_passes_on_lz4_kernel():
    """``_prep_kernel`` must be in R6's traced-function set (the lint is
    not vacuous for this file) and the file must lint clean — a host
    sync or numpy materialization added to the kernel body fails here
    before it fails in CI's tracecheck job."""
    import ast

    from tools.tracecheck import run_paths
    from tools.tracecheck.rules_flow import R6JitPurity, _traced_functions

    tree = ast.parse(KERNEL_FILE.read_text())
    traced = _traced_functions(tree)
    assert "_prep_kernel" in traced
    assert traced["_prep_kernel"][1] == "pallas_call"
    diags = run_paths([str(KERNEL_FILE)], [R6JitPurity()],
                      repo_root=REPO_ROOT)
    assert diags == [], "\n".join(d.format() for d in diags)
