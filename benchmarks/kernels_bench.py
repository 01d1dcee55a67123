"""Kernel-layer benchmark: bytes-scaling + throughput of the TRACE kernels.

Wall-clock on CPU interpret mode is NOT TPU performance; the meaningful
numbers here are (i) bytes moved per view (the paper's proportional-fetch
claim, exact by construction) and (ii) oracle agreement.  We also time the
jnp fallback path to show the host-side cost structure.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import elastic_matmul, elastic_unpack
from repro.kernels import ref as kref

from .common import emit, timed


def write_path(smoke: bool = False):
    """Write-path (encode) throughput: scalar O(blocks x planes) pipeline
    vs the batched slab encoder, on a KV flush through the TRACE device.

    Emits blocks/s and MB/s for both paths plus the measured speedup and
    bypass rate.  The acceptance workload is a 64-block KV flush (64
    windows of 64 tokens x 64 channels); ``smoke`` shrinks it so CI can
    catch encode regressions fast under ``-m "not slow"`` timing.
    """
    import time

    import numpy as np

    from repro.core import synth
    from repro.core.tier import KV, TierStore, WriteReq

    pages, tokens, ch = (12, 16, 32) if smoke else (64, 64, 64)
    reps = 2 if smoke else 4
    data = [synth.kv_cache(tokens, ch, seed=100 + i) for i in range(pages)]
    reqs = [WriteReq(f"p{i}", d, kind=KV) for i, d in enumerate(data)]
    mbytes = pages * tokens * ch * 2 / 1e6

    def run_once(batched):
        dev = TierStore(layout="bitplane-kv", kv_window=tokens,
                        batched_encode=batched)
        t0 = time.perf_counter()
        dev.submit(reqs)
        return time.perf_counter() - t0, dev

    run_once(False), run_once(True)          # warm both paths
    t_scalar = min(run_once(False)[0] for _ in range(reps))
    t_batched, dev_b = float("inf"), None
    for _ in range(reps):
        t, dev = run_once(True)
        if t < t_batched:
            t_batched, dev_b = t, dev
    blocks = dev_b.stats.blocks
    emit("write", "encode_scalar_blocks_per_s", blocks / t_scalar, "blocks/s",
         f"{pages}-page KV flush, per-block pack+codec")
    emit("write", "encode_scalar_mb_per_s", mbytes / t_scalar, "MB/s")
    emit("write", "encode_batched_blocks_per_s", blocks / t_batched,
         "blocks/s", "same flush, vectorized slab encode")
    emit("write", "encode_batched_mb_per_s", mbytes / t_batched, "MB/s")
    emit("write", "encode_batched_speedup", t_scalar / t_batched, "x",
         "byte-identical stored payloads (differential-tested)")
    emit("write", "encode_bypass_rate", dev_b.stats.bypass_rate, "",
         "payload streams stored raw via pre-screen/threshold (§III-D)")
    # regression gating moved to tools/bench_diff.py: the smoke run used
    # to hard-fail on batched >= scalar here, but a committed-baseline
    # tolerance band catches slow drift the binary check missed


def lz4_encode_path(smoke: bool = False):
    """Codec-stage LZ4 throughput: vectorized match kernel vs the PR 3
    fused slab encoder (the scalar oracle behind ``TRACE_SCALAR_LZ4``).

    Captures the exact (slab, starts, ends) codec calls a KV flush
    makes, asserts byte identity between the two paths over the whole
    flush, then times both best-of-N in one process — the *ratio* is
    stable on noisy shared hosts even when absolute times swing, which
    is what lets ``tools/bench_diff.py`` gate the speedup row.  The
    workload is NOT shrunk under ``smoke``: sub-KB streams would time
    kernel dispatch overhead instead of the match path, and the full
    flush costs well under a second.
    """
    from repro.core import codec, synth
    from repro.core.tier import KV, TierStore, WriteReq
    from repro.kernels import lz4 as klz4

    pages, tokens, ch = 64, 64, 64
    # best-of over enough reps to shake scheduler noise out of the gated
    # speedup row (one rep is ~60ms for both paths together)
    reps = 9 if smoke else 15
    captured = []
    orig = codec._lz4_slab_streams

    def spy(buf, starts, ends, force=None):
        captured.append((np.array(buf), np.array(starts), np.array(ends)))
        return orig(buf, starts, ends, force=force)

    codec._lz4_slab_streams = spy
    try:
        dev = TierStore(layout="bitplane-kv", kv_window=tokens,
                        batched_encode=True)
        data = [synth.kv_cache(tokens, ch, seed=100 + i)
                for i in range(pages)]
        dev.submit([WriteReq(f"p{i}", d, kind=KV)
                    for i, d in enumerate(data)])
    finally:
        codec._lz4_slab_streams = orig
    nstreams = sum(s.size for _, s, _ in captured)
    nbytes = sum(int((e - s).sum()) for _, s, e in captured)

    def run_kernel():
        # the production kernel path end to end: gap compaction + match
        # kernel + ragged emit (klz4 imported above pins availability)
        assert klz4 is not None
        return [codec._lz4_slab_streams(buf, s, e)
                for buf, s, e in captured]

    def run_scalar():
        # exactly the PR 3 fallback in codec._lz4_slab_streams: the slab
        # addresses streams with gaps (bypassed ones), so the fused
        # encoder gets the materialized gapless concatenation it expects
        out = []
        for buf, s, e in captured:
            chunks = [buf[a:b].tobytes() for a, b in zip(s, e)]
            out.append(codec._lz4_compress_slab(
                np.frombuffer(b"".join(chunks), dtype=np.uint8), chunks))
        return out

    identical = run_kernel() == run_scalar()     # also warms both paths
    emit("write", "lz4_kernel_byte_identical", int(identical), "bool",
         "kernel path vs scalar oracle, whole flush")
    if not identical:
        raise SystemExit("lz4 kernel/oracle byte divergence")
    _, t_k = timed(run_kernel, reps=reps)
    _, t_s = timed(run_scalar, reps=reps)
    emit("write", "lz4_scalar_streams_per_s", nstreams / t_s, "streams/s",
         "PR 3 fused slab encoder (TRACE_SCALAR_LZ4 oracle)")
    emit("write", "lz4_kernel_streams_per_s", nstreams / t_k, "streams/s",
         "vectorized match kernel + ragged emit")
    emit("write", "lz4_kernel_mb_per_s", nbytes / t_k / 1e6, "MB/s")
    emit("write", "lz4_kernel_speedup", t_s / t_k, "x",
         "gated >= 2x by tools/bench_diff.py")


def run(smoke: bool = False):
    write_path(smoke=smoke)
    lz4_encode_path(smoke=smoke)
    if smoke:
        return
    key = jax.random.PRNGKey(0)
    kx, kw = jax.random.split(key)
    M, K, N = 128, 1024, 512
    x = jax.random.normal(kx, (M, K), jnp.bfloat16)
    w = jax.random.normal(kw, (K, N), jnp.bfloat16)
    planes = kref.pack_weights_kmajor(w)
    dense = np.asarray(jnp.dot(x, w, preferred_element_type=jnp.float32))

    for r_m, d_m in ((7, 0), (4, 1), (2, 1), (0, 1)):
        nplanes = 1 + 8 + min(r_m + d_m, 7)
        frac = nplanes / 16
        out = np.asarray(elastic_matmul(x, planes, r_m=r_m, d_m=d_m))
        rel = np.abs(out - dense).mean() / (np.abs(dense).mean() + 1e-12)
        emit("kernels", f"elastic_matmul_rm{r_m}_weight_bytes_frac", frac,
             "of bf16", "HBM→VMEM bytes ∝ planes fetched")
        emit("kernels", f"elastic_matmul_rm{r_m}_rel_err", float(rel), "")

    # oracle agreement timing (jnp fallback path)
    _, t_ref = timed(
        lambda: jax.block_until_ready(
            kref.elastic_matmul_ref(x, planes, 4, 1)), reps=3
    )
    emit("kernels", "elastic_matmul_ref_jnp_ms", t_ref * 1e3, "ms",
         "host fallback path (CPU)")

    # fp8-KV decode attention: cache bytes halve, oracle agreement holds
    from repro.kernels import decode_attention
    from repro.kernels.ref import decode_attention_ref

    kq, kk, kv2 = jax.random.split(jax.random.PRNGKey(7), 3)
    qd = jax.random.normal(kq, (1, 8, 128), jnp.bfloat16)
    k16 = jax.random.normal(kk, (1, 1024, 2, 128), jnp.bfloat16)
    v16 = jax.random.normal(kv2, (1, 1024, 2, 128), jnp.bfloat16)
    k8, v8 = k16.astype(jnp.float8_e4m3fn), v16.astype(jnp.float8_e4m3fn)
    out8 = np.asarray(decode_attention(qd, k8, v8, valid_len=900))
    ref16 = np.asarray(decode_attention_ref(qd, k16, v16, 900))
    emit("kernels", "decode_attn_fp8_cache_bytes_frac",
         (k8.nbytes + v8.nbytes) / (k16.nbytes + v16.nbytes), "of bf16",
         "HBM traffic = stored precision")
    emit("kernels", "decode_attn_fp8_vs_bf16_rel_err",
         float(np.abs(out8 - ref16).mean() / (np.abs(ref16).mean() + 1e-9)),
         "", "quality cost of fp8 KV storage")

    # unpack view correctness proxy: planes zeroed == bytes not moved
    xu = jax.random.randint(key, (64, 1024), 0, 1 << 16, jnp.uint32).astype(jnp.uint16)
    from repro.kernels import bitplane_pack

    st = bitplane_pack(xu)
    full = np.asarray(elastic_unpack(st))
    np.testing.assert_array_equal(full, np.asarray(xu))
    emit("kernels", "bitplane_roundtrip_bitexact", 1, "bool")


if __name__ == "__main__":
    import sys

    run(smoke="--smoke" in sys.argv)
    from .common import dump_json

    dump_json("kernels_bench")         # no-op unless BENCH_JSON_DIR is set
