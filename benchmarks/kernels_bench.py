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


def run():
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
    run()
    from .common import dump_json

    dump_json("kernels_bench")         # no-op unless BENCH_JSON_DIR is set
