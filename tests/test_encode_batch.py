"""Batched encode pipeline: parity with the per-block reference, bypass
accounting.

The hard invariant of the write path: for every layout, the vectorized
encoder (one plane pack + one ``compress_slab`` per encode slab, batched
KV transform) produces byte-identical stored payloads, flags, index
entries and receipts to the per-block reference pipeline
(``encode_ref.encode_blocks_ref``).  Also covers ``compress_slab`` against
the per-block ``encode_ref.compress_block`` and the bypass pre-screen /
threshold accounting.
"""

import functools

import numpy as np
import pytest

import encode_ref
from repro import obs
from repro.core import codec, synth
from repro.core.bitplane import pack_planes
from repro.core.kv_transform import kv_forward, kv_forward_batch
from repro.core.tier import (
    KV,
    LAYOUTS,
    ReadReq,
    TENSOR,
    TierStore,
    WriteReq,
)
from repro.kernels import dispatch
from repro.kernels.bitplane import kv_pack_planes_slab

RECEIPT_FIELDS = (
    "dram_bytes_read", "dram_bytes_written", "dram_bytes_stored",
    "raw_bytes_stored", "link_bytes_in", "link_bytes_out",
    "index_bytes", "index_hits", "index_misses", "blocks",
    "codec_blocks", "codec_bypass",
)


def _mixed_write_batch(kv_window):
    return [
        WriteReq("w0", synth.weights(6_000, seed=0)),
        WriteReq("s0", synth.kv_cache(2 * kv_window, 64, seed=1), kind=KV),
        WriteReq("w1", synth.weights(2_048, seed=2)),
        WriteReq("s1", synth.kv_cache(kv_window, 32, seed=3), kind=KV),
        WriteReq("s0", synth.kv_cache(kv_window, 64, seed=4), kind=KV),
        WriteReq("part", synth.kv_cache(kv_window // 2, 32, seed=5),
                 kind=KV, flush=False),
        # random (incompressible) payload exercises the bypass pre-screen
        WriteReq("rnd", np.random.default_rng(9).integers(
            0, 1 << 16, 4096).astype(np.uint16)),
    ]


def _storage_state(dev):
    """Everything a differential comparison should see: per-key payload
    bytes + flags + block geometry + KV metadata + shapes."""
    out = {}
    for key, blocks in dev._tensors.items():
        out[key] = [
            (b.payloads, b.flags, b.valid_elems, b.padded_elems,
             None if b.kv_meta is None else
             (b.kv_meta.beta.tobytes(), b.kv_meta.n_tokens,
              b.kv_meta.n_channels))
            for b in blocks
        ]
    return out, dict(dev._shapes)


@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_batched_encode_byte_identical_to_scalar(layout, monkeypatch):
    """Stored bytes, index entries and receipts agree exactly between the
    layout's encoder and the per-block reference run through the same
    store, for sync (reference) and async (production) write posting."""
    kv_window = 16
    scalar_dev = TierStore(layout=layout, kv_window=kv_window)
    monkeypatch.setattr(scalar_dev.layout, "encode_batch", functools.partial(
        encode_ref.encode_blocks_ref, scalar_dev.layout))
    batched_dev = TierStore(layout=layout, kv_window=kv_window)
    batch = _mixed_write_batch(kv_window)
    s_recs = scalar_dev.submit(batch)
    b_recs = [t.wait() for t in batched_dev.submit_async(batch)]

    assert _storage_state(scalar_dev) == _storage_state(batched_dev)
    for s, b in zip(s_recs, b_recs):
        for f in RECEIPT_FIELDS:
            assert getattr(s, f) == getattr(b, f), f
    for f in RECEIPT_FIELDS:
        assert getattr(scalar_dev.stats, f) == getattr(batched_dev.stats, f)

    # ... and reads of the stored data agree bit for bit
    for key, kind in (("w0", TENSOR), ("s0", KV), ("part", KV)):
        a, = scalar_dev.submit([ReadReq(key, kind=kind)])
        b, = batched_dev.submit([ReadReq(key, kind=kind)])
        np.testing.assert_array_equal(a.data, b.data)


@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_single_vs_multi_request_posting_identical(layout):
    """Slab-batching across a posting group must not change what any
    individual request stores: one submit of N writes == N submits."""
    kv_window = 16
    one = TierStore(layout=layout, kv_window=kv_window)
    many = TierStore(layout=layout, kv_window=kv_window)
    batch = _mixed_write_batch(kv_window)
    one.submit(batch)
    for req in batch:
        many.submit([req])
    assert _storage_state(one) == _storage_state(many)


@pytest.mark.parametrize("name", sorted(codec.CODECS))
def test_compress_batch_matches_compress_block(name):
    """``compress_slab`` stores what the per-block reference stores, under
    every registered codec, and the payloads round-trip."""
    rng = np.random.default_rng(0)
    chunks = [
        bytes(rng.integers(0, 256, 4096, dtype=np.uint8)),   # incompressible
        b"\x00" * 4096,                                       # pure run
        b"abcd" * 1024,                                       # periodic
        b"",                                                  # empty
        b"xy",                                                # tiny
        bytes(np.tile(rng.integers(0, 256, 97).astype(np.uint8), 50)),
        (b"the quick brown fox jumps over the lazy dog. " * 120)[:4096],
    ]
    pays, flags = codec.compress_slab(*encode_ref.slab_of(chunks), name)
    assert list(zip(pays, flags)) == [
        encode_ref.compress_block(c, name) for c in chunks]
    assert codec.decompress_batch(pays, flags, name,
                                  [len(c) for c in chunks]) == chunks


def test_compress_slab_identical_to_reference_on_kv_flush(monkeypatch):
    """The exact ``(slab, starts, ends)`` calls that a 64-window KV flush
    through ``bitplane-kv`` makes store what the per-block reference
    stores, stream for stream."""
    calls = []
    compress_slab = codec.compress_slab

    def spy(slab, starts, ends, name, force=None):
        out = compress_slab(slab, starts, ends, name, force)
        calls.append((np.array(slab), np.array(starts), np.array(ends),
                      name, out))
        return out

    monkeypatch.setattr(codec, "compress_slab", spy)
    pages = synth.kv_cache(64 * 16, 128, seed=24).reshape(64, 16, 128)
    TierStore(layout="bitplane-kv", kv_window=16).submit(
        [WriteReq(f"p{i}", p, kind=KV) for i, p in enumerate(pages)])
    assert calls and sum(c[1].size for c in calls) == 64 * 16
    for slab, starts, ends, name, (pays, flags) in calls:
        assert list(zip(pays, flags)) == [
            encode_ref.compress_block(slab[s:e].tobytes(), name)
            for s, e in zip(starts, ends)]
    assert {codec.RAW, codec.COMPRESSED} <= {
        f for c in calls for f in c[4][1]}


def test_lz4_batch_identity_random_battery():
    rng = np.random.default_rng(1)
    battery = [bytes(rng.integers(0, hi, n, dtype=np.uint8))
               for hi in (2, 4, 256)
               for n in (0, 1, 5, 12, 13, 127, 128, 255, 512, 4096)]
    scalar = [encode_ref.lz4_compress(c) for c in battery]
    batched = codec._lz4_slab_streams(*encode_ref.slab_of(battery))
    assert scalar == batched
    for data, comp in zip(battery, scalar):
        if data:
            assert codec.lz4_decompress(comp, max_out=len(data)) == data


def test_prescreen_routes_incompressible_to_bypass():
    rng = np.random.default_rng(2)
    noise = bytes(rng.integers(0, 256, 2048, dtype=np.uint8))
    assert encode_ref.prescreen_bypass(noise)
    assert encode_ref.compress_block(noise, "lz4") == (noise, codec.RAW)
    # compressible payloads must never be pre-screened away
    compressible = (b"\x00" * 2048, b"ab" * 1024,
                    bytes(np.tile(rng.integers(0, 256, 256).astype(np.uint8),
                                  16)))
    for data in compressible:
        assert not encode_ref.prescreen_bypass(data)
        _, fl = encode_ref.compress_block(data, "lz4")
        assert fl == codec.COMPRESSED
    # short blocks skip the screen entirely
    assert not encode_ref.prescreen_bypass(noise[:64])
    # compress_slab makes the same decisions over one slab
    pays, flags = codec.compress_slab(
        *encode_ref.slab_of((noise,) + compressible), "lz4")
    assert pays[0] == noise
    assert flags == [codec.RAW] + [codec.COMPRESSED] * len(compressible)


def test_bypass_threshold_and_counters():
    """BYPASS_THRESHOLD is the documented bypass rule; receipts and
    DeviceStats expose per-block bypass counts (paper §III-D)."""
    assert codec.BYPASS_THRESHOLD == 1.0   # never store an expanded block
    dev = TierStore(layout="bitplane-kv", kv_window=32)
    rec, = dev.submit([WriteReq("s", synth.kv_cache(64, 64, seed=7),
                                kind=KV)])
    # 16 plane streams per committed block went through the bypass rule
    assert rec.codec_blocks == rec.blocks * 16
    assert 0 < rec.codec_bypass < rec.codec_blocks
    assert dev.stats.codec_blocks == rec.codec_blocks
    assert dev.stats.codec_bypass == rec.codec_bypass
    assert 0.0 < dev.stats.bypass_rate < 1.0
    # uncompressed layouts never consult the codec
    plain = TierStore(layout="word")
    prec, = plain.submit([WriteReq("w", synth.weights(2048, seed=1))])
    assert prec.codec_blocks == prec.codec_bypass == 0
    assert plain.stats.bypass_rate == 0.0


def test_kv_forward_batch_matches_scalar():
    wins = np.stack([synth.kv_cache(16, 32, seed=i) for i in range(6)])
    streams, metas = kv_forward_batch(wins)
    for i in range(len(wins)):
        s, m = kv_forward(wins[i])
        np.testing.assert_array_equal(streams[i], s)
        np.testing.assert_array_equal(metas[i].beta, m.beta)
        assert (metas[i].n_tokens, metas[i].n_channels) == (m.n_tokens,
                                                            m.n_channels)


def test_pack_planes_slab_pallas_matches_numpy():
    from repro.core.bitplane import pack_planes
    from repro.kernels.bitplane import pack_planes_slab

    rng = np.random.default_rng(3)
    for n in (64, 2048, 2048 * 3, 97 * 8):
        flat = rng.integers(0, 1 << 16, n).astype(np.uint16)
        np.testing.assert_array_equal(pack_planes_slab(flat),
                                      pack_planes(flat))
        # the pallas kernel path (interpret mode on CPU) packs identically
        np.testing.assert_array_equal(
            pack_planes_slab(flat, force="pallas"), pack_planes(flat))


def _bf16(rng, exp, shape):
    """uint16 BF16 patterns with the given exponent field and random sign
    and mantissa."""
    sign = rng.integers(0, 2, shape).astype(np.uint16) << 15
    man = rng.integers(0, 128, shape).astype(np.uint16)
    return sign | (np.asarray(exp, dtype=np.uint16) << 7) | man


def _tied_windows(rng, B, n, C):
    """Every channel holds two exponents, n // 2 tokens each, shuffled:
    the mode is a tie in every group, broken to the smaller exponent."""
    out = []
    for _ in range(B):
        pair = rng.integers(100, 140, (2, C))
        pair[1] = np.where(pair[1] == pair[0], pair[0] + 1, pair[1])
        exp = np.repeat(pair, n // 2, axis=0)            # (n, C)
        exp = np.take_along_axis(exp, rng.permuted(
            np.tile(np.arange(n)[:, None], (1, C)), axis=0), axis=0)
        out.append(_bf16(rng, exp, (n, C)))
    return out


def _special_windows(rng, B, n, C):
    """±Inf (0x7F80 / 0xFF80) and NaN patterns among normal values, and
    one window of NaNs alone: exponent 0xFF as a minority and as mode."""
    out = []
    specials = np.array([0x7F80, 0xFF80, 0x7FC0, 0xFFC1, 0x7F81],
                        dtype=np.uint16)
    for b in range(B - 1):
        w = _bf16(rng, rng.integers(118, 130, (n, C)), (n, C))
        hit = rng.random((n, C)) < (0.1 if b % 2 else 0.7)
        w[hit] = rng.choice(specials, hit.sum())
        out.append(w)
    out.append(np.full((n, C), 0x7FC0, dtype=np.uint16))
    return out


_KV_SLABS = {
    # B not a power of two: the padded windows are sliced off
    "n16_decode_boundary": lambda rng: list(
        synth.kv_cache(48 * 16, 128, seed=11).reshape(48, 16, 128)),
    "n64": lambda rng: [synth.kv_cache(64, 32, seed=12 + i)
                        for i in range(5)],
    "ties": lambda rng: _tied_windows(rng, 3, 16, 128),
    "equal_and_zero": lambda rng: [
        np.full((16, 128), 0x3F80, dtype=np.uint16),
        np.zeros((16, 128), dtype=np.uint16),
        np.full((16, 128), 0xC2F7, dtype=np.uint16)],
    "inf_nan": lambda rng: _special_windows(rng, 7, 64, 16),
}


@pytest.mark.parametrize("case", sorted(_KV_SLABS))
def test_kv_pack_planes_slab_matches_numpy_twin(case):
    """The device program (pallas interpret mode here) gives the planes
    and betas of ``kv_forward_batch`` + ``pack_planes``, byte for byte."""
    windows = _KV_SLABS[case](np.random.default_rng(7))
    planes, beta = kv_pack_planes_slab(windows, interpret=True)
    streams, metas = kv_forward_batch(np.stack(windows))
    assert planes.dtype == beta.dtype == np.uint8
    np.testing.assert_array_equal(planes, pack_planes(streams.ravel()))
    np.testing.assert_array_equal(beta, np.stack([m.beta for m in metas]))


def _spill_group(mixed: bool):
    """One admission's spill: 384 page windows of (16, 128), one request
    each; ``mixed`` puts a TENSOR write first, so the first encode slab
    holds a tensor block beside 63 windows."""
    pages = synth.kv_cache(384 * 16, 128, seed=21).reshape(384, 16, 128)
    reqs = [WriteReq(f"L{i // 16}.p{i % 16}", p, kind=KV, flush=True)
            for i, p in enumerate(pages)]
    if mixed:
        reqs.insert(0, WriteReq("w", synth.weights(2048, seed=22)))
    return reqs


def _pin_kv_pack(monkeypatch, force):
    """Pin ``dispatch.choose``'s path for ``kv_pack`` alone."""
    choose = dispatch.choose
    monkeypatch.setattr(dispatch, "choose", lambda kernel, f=None: choose(
        kernel, force if kernel == "kv_pack" else f))


@pytest.mark.parametrize("mixed", [False, True], ids=["spill", "mixed"])
def test_kv_pack_device_path_stores_what_the_twin_stores(mixed, monkeypatch):
    """A spill posting group through the device transform+pack and
    through the numpy twin: identical payloads, flags, metas, receipts
    and read-back."""
    reqs = _spill_group(mixed)
    twin = TierStore(layout="bitplane-kv", kv_window=16)
    dev = TierStore(layout="bitplane-kv", kv_window=16)
    t_recs = twin.submit(reqs)
    _pin_kv_pack(monkeypatch, "pallas")
    d_recs = dev.submit(reqs)
    assert _storage_state(twin) == _storage_state(dev)
    for t, d in zip(t_recs, d_recs):
        for f in RECEIPT_FIELDS:
            assert getattr(t, f) == getattr(d, f), f
    keys = [(r.key, r.kind) for r in reqs[:: 37]] + [(reqs[-1].key, KV)]
    reads = [ReadReq(k, kind=kind) for k, kind in keys]
    for a, b, (k, _) in zip(twin.submit(reads), dev.submit(reads), keys):
        np.testing.assert_array_equal(a.data, b.data)
        np.testing.assert_array_equal(
            b.data.ravel(), next(r.data for r in reqs if r.key == k).ravel())


@pytest.mark.parametrize("force, host, decisions", [
    ("pallas", 0, {"kernel.kv_pack.interpret": 2}),
    (None, 80, {"kernel.kv_pack.numpy": 2}),
], ids=["device", "cpu"])
def test_kv_host_windows_counter(force, host, decisions, monkeypatch):
    """``tier.kv_host_windows`` counts the windows transformed on the
    host; a slab of windows alone is one ``kv_pack`` decision."""
    pages = synth.kv_cache(80 * 16, 128, seed=23).reshape(80, 16, 128)
    _pin_kv_pack(monkeypatch, force)
    dev = TierStore(layout="bitplane-kv", kv_window=16)
    obs.reset()
    dev.submit([WriteReq(f"p{i}", p, kind=KV) for i, p in enumerate(pages)])
    counters = obs.snapshot()["counters"]
    assert counters.get("tier.kv_host_windows", 0) == host
    assert {k: n for k, n in counters.items()
            if k.startswith("kernel.kv_pack.")} == decisions
