"""Model building blocks: norms, RoPE, (chunked/windowed) GQA attention,
MLA attention, MLP variants, MoE dispatch, Mamba1/Mamba2 blocks.

All functions are pure; parameters arrive as dicts of jnp arrays.  Heavy
attention paths avoid materialising the full (Sq, Sk) score matrix across
the whole sequence by scanning query chunks (online per-chunk softmax over
the full key range), which keeps peak activation memory ∝ chunk * Sk.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from .sharding import current_rules, logical


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------

def rmsnorm(x, scale, eps=1e-6):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    out = x.astype(jnp.float32) * jax.lax.rsqrt(var + eps)
    return (out * (1.0 + scale.astype(jnp.float32))).astype(x.dtype)


def layernorm(x, scale, bias, eps=1e-5):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    out = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (out * (1.0 + scale) + bias).astype(x.dtype)


def norm(x, p, kind: str, eps: float = 1e-6):
    """``kind`` norm of ``x``; ``eps`` is the RMSNorm epsilon."""
    if kind == "layernorm":
        return layernorm(x, p["scale"], p["bias"])
    return rmsnorm(x, p["scale"], eps)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope(x, positions, theta: float = 1e4):
    """x: (..., S, H, hd); positions: (..., S) int32."""
    hd = x.shape[-1]
    half = hd // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * freqs      # (..., S, half)
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    )
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------

def _masked_softmax(scores, mask):
    scores = jnp.where(mask, scores, -1e30)
    return jax.nn.softmax(scores.astype(jnp.float32), axis=-1)


def query_chunk(S: int) -> int:
    """Query positions per chunk that attention over ``S`` queries scans
    (0: one block).  Prompts past 1024 tokens scan chunks of 256, which
    bounds the score matrix to 256 x Sk."""
    return 256 if S > 1024 and S % 256 == 0 else 0


def attention(
    q, k, v,
    *,
    causal: bool = True,
    window: int = 0,
    q_positions=None,
    k_positions=None,
    kv_valid_len=None,
    q_chunk: int = 0,
):
    """GQA attention.

    q: (B, Sq, H, hd);  k, v: (B, Sk, KV, hd) with H % KV == 0.
    ``window`` > 0 applies sliding-window attention (zamba2 long-context).
    ``kv_valid_len``: (B,) or scalar — mask out cache slots beyond it.
    ``q_chunk`` > 0 scans query chunks of that size (a divisor of Sq)
    to bound score memory; callers take it from :func:`query_chunk`.
    """
    B, Sq, H, hd = q.shape
    Sk, KV = k.shape[1], k.shape[2]
    rep = H // KV
    scale = hd ** -0.5
    if q_positions is None:
        q_positions = jnp.arange(Sq)[None, :]
    if k_positions is None:
        k_positions = jnp.arange(Sk)[None, :]

    qg = q.reshape(B, Sq, KV, rep, hd) * scale

    def block(qb, qpos):
        # qb: (B, C, KV, rep, hd) → scores (B, KV, rep, C, Sk)
        s = jnp.einsum("bqgrd,bkgd->bgrqk", qb.astype(jnp.float32),
                       k.astype(jnp.float32))
        m = jnp.ones((B, 1, 1, qb.shape[1], Sk), dtype=bool)
        if causal:
            m &= (k_positions[:, None, None, None, :]
                  <= qpos[:, None, None, :, None])
        if window:
            m &= (k_positions[:, None, None, None, :]
                  > qpos[:, None, None, :, None] - window)
        if kv_valid_len is not None:
            lim = jnp.asarray(kv_valid_len).reshape(-1, 1, 1, 1, 1)
            m &= k_positions[:, None, None, None, :] < lim
        p = _masked_softmax(s, m)
        o = jnp.einsum("bgrqk,bkgd->bqgrd", p, v.astype(jnp.float32))
        return o.astype(q.dtype)

    dv = v.shape[-1]  # may differ from hd (MLA)
    if q_chunk:
        nc = Sq // q_chunk
        qc = qg.reshape(B, nc, q_chunk, KV, rep, hd).transpose(1, 0, 2, 3, 4, 5)
        pc = jnp.broadcast_to(q_positions, (B, Sq))
        pc = pc.reshape(B, nc, q_chunk).transpose(1, 0, 2)
        out = jax.lax.map(lambda args: block(*args), (qc, pc))
        out = out.transpose(1, 0, 2, 3, 4, 5).reshape(B, Sq, H, dv)
    else:
        out = block(qg, jnp.broadcast_to(q_positions, (B, Sq)))
        out = out.reshape(B, Sq, H, dv)
    return out


def gqa_block(x, p, cfg, *, positions, cache=None, cache_pos=None,
              window: int = 0):
    """Full attention sub-block: norm → qkv (+rope) → attention → out proj.

    ``cache``: optional dict {k: (B, Smax, KV, hd), v: ...} for decoding —
    the new token's K/V is written at ``cache_pos`` and attention runs over
    the cache.  Returns (out, new_cache).
    """
    B, S, d = x.shape
    H, KVh, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    h = norm(x, p["norm"], cfg.norm, cfg.norm_eps)
    q = jnp.einsum("bsd,dh->bsh", h, p["wq"])
    k = jnp.einsum("bsd,dh->bsh", h, p["wk"])
    v = jnp.einsum("bsd,dh->bsh", h, p["wv"])
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(B, S, H, hd)
    k = k.reshape(B, S, KVh, hd)
    v = v.reshape(B, S, KVh, hd)
    if cfg.causal:  # RoPE only for decoder families
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)

    if cache is None:
        q = logical(q, "batch", "seq", "heads", None)
        k = logical(k, "batch", "seq", "kv_heads", None)
        out = attention(
            q, k, v, causal=cfg.causal, window=window,
            q_positions=positions, q_chunk=query_chunk(S),
        )
        new_cache = None
    else:
        cdt = cache["k"].dtype           # may be fp8 (elastic KV storage)
        kc = jax.lax.dynamic_update_slice_in_dim(
            cache["k"], k.astype(cdt), cache_pos, 1
        )
        vc = jax.lax.dynamic_update_slice_in_dim(
            cache["v"], v.astype(cdt), cache_pos, 1
        )
        kc = logical(kc, "batch", "kv_seq", "kv_heads", None)
        vc = logical(vc, "batch", "kv_seq", "kv_heads", None)
        if "kpos" in cache:
            # rolling-window cache: per-slot absolute positions; the window
            # + causal tests against kpos do all masking (stale slots hold
            # kpos = -2*window → always excluded).
            kpos = jax.lax.dynamic_update_slice_in_dim(
                cache["kpos"],
                jnp.broadcast_to(positions[:, :1], cache["kpos"][:, :1].shape),
                cache_pos, 1,
            )
            valid = None
        else:
            kpos = jnp.arange(kc.shape[1])[None, :]
            valid = cache_pos + S
        out = attention(
            q, kc.astype(k.dtype), vc.astype(v.dtype), causal=True,
            window=window,
            q_positions=positions, k_positions=kpos,
            kv_valid_len=valid,
            q_chunk=query_chunk(S),
        )
        new_cache = {"k": kc, "v": vc}
    out = out.reshape(B, S, H * hd) @ p["wo"]
    return out, new_cache


def mla_block(x, p, cfg, *, positions, cache=None, cache_pos=None):
    """DeepSeek-V2 Multi-head Latent Attention.

    KV is compressed into a per-token latent c_kv (kv_lora_rank) plus a
    shared RoPE key (qk_rope_dim); the cache stores only these (the MLA
    memory win).  Decode re-expands K/V from the latent.
    """
    B, S, d = x.shape
    H, hd, r = cfg.n_heads, cfg.hd, cfg.kv_lora_rank
    rd, vh = cfg.qk_rope_dim, cfg.v_head_dim or cfg.hd
    h = norm(x, p["norm"], cfg.norm, cfg.norm_eps)

    q = (h @ p["wq"]).reshape(B, S, H, hd + rd)
    q_nope, q_rope = q[..., :hd], q[..., hd:]
    q_rope = rope(q_rope, positions, cfg.rope_theta)

    ckv_full = h @ p["w_dkv"]                       # (B, S, r + rd)
    c_kv = rmsnorm(ckv_full[..., :r], p["kv_norm"]["scale"])
    k_rope = rope(ckv_full[..., None, r:], positions, cfg.rope_theta)  # (B,S,1,rd)

    if cache is not None:
        cdt = cache["c_kv"].dtype        # may be fp8 (elastic KV storage)
        c_kv = jax.lax.dynamic_update_slice_in_dim(
            cache["c_kv"], c_kv.astype(cdt), cache_pos, 1
        )
        k_rope = jax.lax.dynamic_update_slice_in_dim(
            cache["k_rope"], k_rope.astype(cdt), cache_pos, 1
        )
        new_cache = {"c_kv": c_kv, "k_rope": k_rope}
        c_kv = c_kv.astype(h.dtype)
        k_rope = k_rope.astype(h.dtype)
        Sk = c_kv.shape[1]
        kv_valid = cache_pos + S
        k_positions = jnp.arange(Sk)[None, :]
    else:
        new_cache = None
        Sk = S
        kv_valid = None
        k_positions = positions

    k_nope = jnp.einsum("bsr,rhd->bshd", c_kv, p["w_uk"].reshape(r, H, hd))
    v = jnp.einsum("bsr,rhd->bshd", c_kv, p["w_uv"].reshape(r, H, vh))
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope, (B, Sk, H, rd))], axis=-1
    )
    qfull = jnp.concatenate([q_nope, q_rope], axis=-1)
    out = attention(
        qfull, k, v, causal=True,
        q_positions=positions, k_positions=k_positions,
        kv_valid_len=kv_valid, q_chunk=query_chunk(S),
    )
    out = out.reshape(B, S, H * vh) @ p["wo"]
    return out, new_cache


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------

def mlp_apply(h, p, kind: str):
    if kind == "swiglu":
        a = h @ p["w1"]
        g = h @ p["w3"]
        z = jax.nn.silu(a) * g
    elif kind == "squared_relu":
        z = jnp.square(jax.nn.relu(h @ p["w1"]))
    else:  # gelu
        z = jax.nn.gelu(h @ p["w1"])
    z = logical(z, "batch", "seq", "mlp")
    return z @ p["w2"]


def mlp_block(x, p, cfg):
    h = norm(x, p["norm"], cfg.norm, cfg.norm_eps)
    return mlp_apply(h, p, cfg.mlp)


# ---------------------------------------------------------------------------
# MoE (top-k routing, capacity-bounded batched expert matmul)
# ---------------------------------------------------------------------------

def _moe_dense_decode(flat, p, cfg, gate_vals, expert_idx):
    """Decode path: activate EVERY expert for the (few) tokens and mask.

    For T = batch tokens this is exact (no capacity drops) and turns the
    dispatch into one batched einsum — the right trade at decode batch
    sizes, and it matches the training math wherever no drop occurred.
    """
    E, k = cfg.n_experts, cfg.top_k
    T, d = flat.shape
    if cfg.mlp == "swiglu":
        a = jnp.einsum("td,edf->etf", flat, p["w1"])
        g = jnp.einsum("td,edf->etf", flat, p["w3"])
        z = jax.nn.silu(a) * g
    else:
        z = jax.nn.gelu(jnp.einsum("td,edf->etf", flat, p["w1"]))
    y_all = jnp.einsum("etf,efd->etd", z, p["w2"])           # (E, T, d)
    weight = jnp.zeros((T, E), jnp.float32).at[
        jnp.arange(T)[:, None], expert_idx
    ].add(gate_vals)
    return jnp.einsum("etd,te->td", y_all, weight.astype(flat.dtype))


def _local_dispatch(flat, cfg, cap, router_w):
    """Top-k routing + capacity-bounded scatter on LOCAL tokens.

    Returns (buf (E, cap, d), e_flat, p_flat, keep, gate_vals, probs)."""
    E, k = cfg.n_experts, cfg.top_k
    T, d = flat.shape
    logits = (flat @ router_w).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_idx = jax.lax.top_k(probs, k)
    gate_vals = gate_vals / jnp.sum(gate_vals, axis=-1, keepdims=True)

    onehot = jax.nn.one_hot(expert_idx, E, dtype=jnp.int32)
    pos_in_expert = (jnp.cumsum(onehot.reshape(T * k, E), axis=0) - 1)
    pos_in_expert = jnp.take_along_axis(
        pos_in_expert.reshape(T, k, E), expert_idx[..., None], axis=-1
    )[..., 0]
    keep = pos_in_expert < cap
    e_flat = jnp.where(keep, expert_idx, E)
    p_flat = jnp.where(keep, pos_in_expert, cap)
    buf = jnp.zeros((E, cap, d), dtype=flat.dtype)
    buf = buf.at[e_flat.reshape(-1), p_flat.reshape(-1)].set(
        jnp.repeat(flat, k, axis=0), mode="drop"
    )
    return buf, e_flat, p_flat, keep, gate_vals, expert_idx, probs


def _expert_ffn(buf, p, cfg, w1, w2, w3):
    if cfg.mlp == "swiglu":
        a = jnp.einsum("ecd,edf->ecf", buf, w1)
        g = jnp.einsum("ecd,edf->ecf", buf, w3)
        z = jax.nn.silu(a) * g
    else:
        z = jax.nn.gelu(jnp.einsum("ecd,edf->ecf", buf, w1))
    return jnp.einsum("ecf,efd->ecd", z, w2)


def _combine(y_buf, e_flat, p_flat, keep, gate_vals, E, cap, d, dtype):
    gathered = y_buf[e_flat.reshape(-1) % E, p_flat.reshape(-1) % cap]
    gathered = jnp.where(keep.reshape(-1, 1), gathered, 0.0)
    T, k = keep.shape
    return (gathered.reshape(T, k, d)
            * gate_vals[..., None].astype(dtype)).sum(axis=1)


def moe_block_ep(x, p, cfg, mesh, token_axes):
    """Expert-parallel MoE via shard_map + all_to_all (§Perf deepseek
    iteration 2 — the production dispatch).

    The jnp scatter in the SPMD path is unpartitionable: XLA replicates
    the GLOBAL (E, cap, d) buffer on every device and all-gathers the
    (T·k, d) token copies (measured: 26 GB/layer/device on the deepseek
    train cell).  Under shard_map the dispatch scatter touches only LOCAL
    tokens; the only cross-device traffic is the (E, C_l, d) all_to_all
    that moves each expert group to its owner — bytes = buf size, not
    tokens × k, and the FFN einsums run at (E/M, M·C_l, d) per device
    with zero redundancy.

    Layout: tokens sharded over ``token_axes`` (= batch axes + 'model');
    experts over 'model'; expert weights FSDP-gathered over 'data' inside
    (standard FSDP all-gather, same as the dense layers).
    """
    from jax.sharding import PartitionSpec as P

    from ..compat import shard_map

    B, S, d = x.shape
    E, k = cfg.n_experts, cfg.top_k
    M = mesh.shape["model"]
    h = norm(x, p["norm"], cfg.norm, cfg.norm_eps)
    flat = h.reshape(B * S, d)
    T = B * S
    nshards = 1
    for a in token_axes:
        nshards *= mesh.shape[a]
    T_l = T // nshards
    cap_l = int(max(1, cfg.capacity_factor * k * T_l / E))

    def body(flat_l, router_l, w1_l, w2_l, *w3_rest):
        if cfg.fsdp:
            # FSDP: gather the d_model shards of this device's expert group
            router_g = jax.lax.all_gather(router_l, "data", axis=0, tiled=True)
            w1_g = jax.lax.all_gather(w1_l, "data", axis=1, tiled=True)
            w2_g = jax.lax.all_gather(w2_l, "data", axis=2, tiled=True)
            w3_g = (jax.lax.all_gather(w3_rest[0], "data", axis=1, tiled=True)
                    if w3_rest else None)
        else:
            router_g, w1_g, w2_g = router_l, w1_l, w2_l
            w3_g = w3_rest[0] if w3_rest else None

        buf, e_flat, p_flat, keep, gates, expert_idx, probs = _local_dispatch(
            flat_l, cfg, cap_l, router_g
        )
        # exchange expert groups: (E, C_l, d) → (E/M, M*C_l, d)
        buf = jax.lax.all_to_all(
            buf, "model", split_axis=0, concat_axis=1, tiled=True
        )
        y = _expert_ffn(buf, p, cfg, w1_g, w2_g, w3_g)
        # return results to token owners: (E/M, M*C_l, d) → (E, C_l, d)
        y = jax.lax.all_to_all(
            y, "model", split_axis=1, concat_axis=0, tiled=True
        )
        out_l = _combine(y, e_flat, p_flat, keep, gates, E, cap_l, d, x.dtype)

        frac_tokens = jnp.mean(
            jax.nn.one_hot(expert_idx[:, 0], E, dtype=jnp.float32), axis=0
        )
        frac_probs = jnp.mean(probs, axis=0)
        for a in token_axes:
            frac_tokens = jax.lax.pmean(frac_tokens, a)
            frac_probs = jax.lax.pmean(frac_probs, a)
        aux = E * jnp.sum(frac_tokens * frac_probs)
        return out_l, aux

    w3 = p.get("w3")
    dd = "data" if cfg.fsdp else None
    in_specs = [
        P(token_axes, None),              # tokens
        P(dd, None),                      # router (d, E): FSDP on d
        P("model", dd, None),             # w1 (E, d, fe)
        P("model", None, dd),             # w2 (E, fe, d)
    ]
    args = [flat, p["router"], p["w1"], p["w2"]]
    if w3 is not None:
        in_specs.append(P("model", dd, None))
        args.append(w3)
    out_flat, aux = shard_map(
        body, mesh=mesh,
        in_specs=tuple(in_specs),
        out_specs=(P(token_axes, None), P()),
        check_vma=False,
    )(*args)

    out = out_flat.reshape(B, S, d)
    if cfg.n_shared_experts:
        out = out + mlp_apply(h, p["shared"], cfg.mlp)
    return out, aux


def moe_block(x, p, cfg):
    """Token-choice top-k MoE with sort-free one-hot dispatch.

    Tokens are flattened, routed to ``top_k`` experts, packed into a
    per-expert capacity buffer via scatter, processed with one batched
    einsum over experts (MXU-friendly), and combined weighted by gates.
    Overflowing tokens are dropped (standard capacity semantics); a
    load-balancing auxiliary loss is returned for training.  Single-token
    steps (decode) use the exact dense-activation path instead.
    """
    B, S, d = x.shape
    E, k = cfg.n_experts, cfg.top_k
    capacity_factor = getattr(cfg, "capacity_factor", 1.25)

    # Distributed training/prefill: use the shard_map EP dispatch whenever
    # the mesh can own experts (E % model == 0) — the SPMD scatter path
    # below replicates the global dispatch buffer on every device.
    r = current_rules()
    if (r is not None and S > 1 and hasattr(r.mesh, "axis_names")
            and "model" in r.mesh.axis_names
            and cfg.n_experts % r.mesh.shape["model"] == 0):
        ba = tuple(a for a in ("pod", "data") if a in r.mesh.axis_names)
        token_axes = ba + ("model",)
        nshards = 1
        for a in token_axes:
            nshards *= r.mesh.shape[a]
        if (B * S) % nshards == 0:
            return moe_block_ep(x, p, cfg, r.mesh, token_axes)

    h = norm(x, p["norm"], cfg.norm, cfg.norm_eps)
    flat = h.reshape(B * S, d)
    T = B * S

    logits = (flat @ p["router"]).astype(jnp.float32)       # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_idx = jax.lax.top_k(probs, k)          # (T, k)
    gate_vals = gate_vals / jnp.sum(gate_vals, axis=-1, keepdims=True)

    if S == 1:
        combined = _moe_dense_decode(flat, p, cfg, gate_vals, expert_idx)
        out = combined.reshape(B, S, d)
        if cfg.n_shared_experts:
            out = out + mlp_apply(h, p["shared"], cfg.mlp)
        return out, jnp.zeros((), jnp.float32)

    cap = int(max(1, capacity_factor * k * T / E))
    # position of each (token, choice) within its expert's buffer
    onehot = jax.nn.one_hot(expert_idx, E, dtype=jnp.int32)  # (T, k, E)
    pos_in_expert = (jnp.cumsum(onehot.reshape(T * k, E), axis=0) - 1)
    pos_in_expert = jnp.take_along_axis(
        pos_in_expert.reshape(T, k, E), expert_idx[..., None], axis=-1
    )[..., 0]                                                # (T, k)
    keep = pos_in_expert < cap

    buf = jnp.zeros((E, cap, d), dtype=x.dtype)
    e_flat = jnp.where(keep, expert_idx, E)                  # drop → OOB
    p_flat = jnp.where(keep, pos_in_expert, cap)
    buf = buf.at[e_flat.reshape(-1), p_flat.reshape(-1)].set(
        jnp.repeat(flat, k, axis=0), mode="drop"
    )
    # capacity must shard over the data axis: with only experts sharded,
    # every data-shard would redundantly compute the FULL global capacity
    # (16x wasted MXU flops at mesh 16x16 — §Perf deepseek iteration 1)
    buf = logical(buf, "experts", "capacity", "embed")

    if cfg.mlp == "swiglu":
        a = jnp.einsum("ecd,edf->ecf", buf, p["w1"])
        g = jnp.einsum("ecd,edf->ecf", buf, p["w3"])
        z = jax.nn.silu(a) * g
    else:
        z = jax.nn.gelu(jnp.einsum("ecd,edf->ecf", buf, p["w1"]))
    z = logical(z, "experts", "capacity", "moe_mlp")
    y_buf = jnp.einsum("ecf,efd->ecd", z, p["w2"])           # (E, cap, d)

    gathered = y_buf[e_flat.reshape(-1) % E, p_flat.reshape(-1) % cap]
    gathered = jnp.where(keep.reshape(-1, 1), gathered, 0.0)
    combined = (gathered.reshape(T, k, d)
                * gate_vals[..., None].astype(x.dtype)).sum(axis=1)

    out = combined.reshape(B, S, d)
    if cfg.n_shared_experts:
        out = out + mlp_apply(h, p["shared"], cfg.mlp)

    # load-balance aux loss (Switch-style)
    frac_tokens = jnp.mean(
        jax.nn.one_hot(expert_idx[:, 0], E, dtype=jnp.float32), axis=0
    )
    frac_probs = jnp.mean(probs, axis=0)
    aux = E * jnp.sum(frac_tokens * frac_probs)
    return out, aux


# ---------------------------------------------------------------------------
# Mamba1 (falcon-mamba)
# ---------------------------------------------------------------------------

def causal_conv1d(x, w, b, state=None):
    """x: (B, S, C), depthwise kernel w: (C, K).  If ``state`` (B, K-1, C)
    is given, run incrementally (decode) and return (y, new_state)."""
    B, S, C = x.shape
    K = w.shape[-1]
    if state is not None:
        xin = jnp.concatenate([state, x], axis=1)           # (B, K-1+S, C)
        new_state = xin[:, -(K - 1):, :]
    else:
        xin = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
        new_state = None
    y = jax.lax.conv_general_dilated(
        xin, w.T[:, None, :],                                # (K, 1, C)
        window_strides=(1,), padding="VALID",
        dimension_numbers=("NWC", "WIO", "NWC"),
        feature_group_count=C,
    )
    return y + b, new_state


def mamba1_mix(x, p, cfg, state=None):
    """Mamba1 mixer.  x: (B, S, d).  ``state``: dict(conv, ssm) for decode.
    Returns (y, new_state)."""
    B, S, d = x.shape
    di, N = cfg.d_inner, cfg.ssm_state
    dt_rank = max(d // 16, 1)

    xz = x @ p["in_proj"]                                   # (B, S, 2*di)
    xs, z = xz[..., :di], xz[..., di:]
    xs = logical(xs, "batch", "seq", "d_inner")

    conv_state = state["conv"] if state is not None else None
    xs, new_conv = causal_conv1d(xs, p["conv_w"], p["conv_b"], conv_state)
    xs = jax.nn.silu(xs)

    xdbc = xs @ p["x_proj"]                                  # (B,S,dt_rank+2N)
    dt = jax.nn.softplus(
        xdbc[..., :dt_rank] @ p["dt_proj"] + p["dt_bias"]
    )                                                        # (B, S, di)
    Bm = xdbc[..., dt_rank : dt_rank + N]                    # (B, S, N)
    Cm = xdbc[..., dt_rank + N :]                            # (B, S, N)
    A = -jnp.exp(p["A_log"].astype(jnp.float32))             # (di, N)

    dA = jnp.exp(dt[..., None].astype(jnp.float32) * A)      # (B,S,di,N)
    dBx = (dt * xs)[..., None].astype(jnp.float32) * Bm[:, :, None, :]

    h0 = (state["ssm"].astype(jnp.float32) if state is not None
          else jnp.zeros((B, di, N), jnp.float32))

    def step(h, inp):
        dA_t, dBx_t, C_t = inp
        h = dA_t * h + dBx_t
        y = jnp.einsum("bdn,bn->bd", h, C_t)
        return h, y

    hT, ys = jax.lax.scan(
        step,
        h0,
        (dA.transpose(1, 0, 2, 3), dBx.transpose(1, 0, 2, 3),
         Cm.astype(jnp.float32).transpose(1, 0, 2)),
    )
    y = ys.transpose(1, 0, 2).astype(x.dtype)                # (B, S, di)
    y = y + xs * p["D"]
    y = y * jax.nn.silu(z)
    out = y @ p["out_proj"]
    new_state = None
    if state is not None:
        new_state = {"conv": new_conv, "ssm": hT.astype(state["ssm"].dtype)}
    return out, new_state


# ---------------------------------------------------------------------------
# Mamba2 (zamba2 backbone)
# ---------------------------------------------------------------------------

def mamba2_mix(x, p, cfg, state=None):
    """Mamba2 (SSD recurrence, scan form — the chunked-parallel SSD kernel
    is a TPU adaptation noted in DESIGN.md).  x: (B, S, d)."""
    B, S, d = x.shape
    di, N = cfg.d_inner, cfg.ssm_state
    Hm = cfg.ssm_heads or max(di // 64, 1)
    P_ = di // Hm

    proj = x @ p["in_proj"]                  # (B,S, 2*di + 2N + Hm)
    z, xs = proj[..., :di], proj[..., di : 2 * di]
    Bm = proj[..., 2 * di : 2 * di + N]
    Cm = proj[..., 2 * di + N : 2 * di + 2 * N]
    dt = jax.nn.softplus(proj[..., 2 * di + 2 * N :] + p["dt_bias"])  # (B,S,Hm)

    conv_in = jnp.concatenate([xs, Bm, Cm], axis=-1)
    conv_state = state["conv"] if state is not None else None
    conv_out, new_conv = causal_conv1d(conv_in, p["conv_w"], p["conv_b"],
                                       conv_state)
    conv_out = jax.nn.silu(conv_out)
    xs = conv_out[..., :di].reshape(B, S, Hm, P_)
    Bm = conv_out[..., di : di + N]
    Cm = conv_out[..., di + N :]

    A = -jnp.exp(p["A_log"].astype(jnp.float32))              # (Hm,)
    dA = jnp.exp(dt.astype(jnp.float32) * A)                  # (B,S,Hm)
    dBx = (dt[..., None] * xs)[..., None].astype(jnp.float32) \
        * Bm[:, :, None, None, :].astype(jnp.float32)         # (B,S,Hm,P,N)

    h0 = (state["ssm"].astype(jnp.float32) if state is not None
          else jnp.zeros((B, Hm, P_, N), jnp.float32))

    def step(h, inp):
        dA_t, dBx_t, C_t = inp
        h = dA_t[..., None, None] * h + dBx_t
        y = jnp.einsum("bhpn,bn->bhp", h, C_t)
        return h, y

    hT, ys = jax.lax.scan(
        step,
        h0,
        (dA.transpose(1, 0, 2), dBx.transpose(1, 0, 2, 3, 4),
         Cm.astype(jnp.float32).transpose(1, 0, 2)),
    )
    y = ys.transpose(1, 0, 2, 3).astype(x.dtype)              # (B,S,Hm,P)
    y = y + xs * p["D"][None, None, :, None]
    y = y.reshape(B, S, di)
    y = rmsnorm(y * jax.nn.silu(z), p["norm_gated"]["scale"])
    out = y @ p["out_proj"]
    new_state = None
    if state is not None:
        new_state = {"conv": new_conv, "ssm": hT.astype(state["ssm"].dtype)}
    return out, new_state
